"""Registry-wide static operator contract auditor (the KP5xx family).

The whole PR 4–6 performance stack — fusion, megafusion, donation, the
concurrent DAG scheduler — rests on contracts operators *declare*
(``fusable``/``fuse()``, ``chunkable``, ``fusable_fit``,
``donates_deps``, ``fuse_masks_output``) and nothing verified: PR 6
found five stages declaring ``fusable`` without a ``fuse()``
decomposition, silently re-tracing every re-apply at ~5× cost. This
module makes those contracts *checked properties* (the
KeystoneML-soundness discipline of arXiv 1610.09451 — the optimizer is
only correct because capability declarations are truthful — enforced as
a compiler-level safety pass in the spirit of arXiv 2206.14148):

  KP501  fusable-without-structural-fuse: a stage declaring ``fusable``
         (or promised through an estimator's ``fusable_fit``) whose
         fused-program key path is id-keyed ("opaque") — detected by
         running the SAME decomposition the fusion builder uses
         (`nodes.util.fusion.stage_fuse`) and inspecting the static
         key, not by naming convention. Opaque keys mean every fused
         program containing the stage is cached per-instance and
         re-traced on every rebuilt pipeline — the PR-6 silent-retrace
         bug class.
  KP502  chunkable-non-distributive: ``chunkable = True`` whose batch
         path provably does not distribute over host chunks — the
         `jax.eval_shape` of the whole-batch form must agree with the
         concatenation of the chunk forms (`specs.trace_element`, zero
         data movement). A batch path that reduces over the example
         axis or grows a non-leading axis with n would return corrupt
         values the moment the overlap engine streams chunks through it.
  KP503  donation-not-implemented: ``donates_deps`` declared but no
         jitted step reachable from the operator's methods carries
         ``donate_argnums`` (or its donated indices exceed the step's
         signature) — the intra-operator complement of the graph-level
         KP301 hazard: the analyzer restricts the producer's consumers
         for a donation that never actually happens.
  KP504  unmasked-fused-stage: a ``fusable`` stage whose *unfused*
         batch path consumes the dataset's padded-row ``mask`` but
         which does not declare ``fuse_masks_output`` — inside a fused
         program the stage would stop re-zeroing padded rows and
         mask-less reductions downstream (`_moments`,
         `_normal_equations`) would read garbage: the padded-row
         corruption class PR 4's review caught by hand.

Two surfaces:

  - ``contract_pass(graph, specs)`` — instance-level checks over every
    operator in a lowered graph, run by ``validate(level="full")``.
  - ``audit_registry()`` / ``python -m keystone_tpu.analysis
    --audit-operators`` — sweeps EVERY registered Operator/Estimator
    subclass (probe instances where construction is known, class-level
    AST checks otherwise), so a new operator inherits the gate without
    ever appearing in an example pipeline.

Genuine exceptions suppress with a ``# keystone: ignore[KP50x]``
comment on the ``class`` line (mirroring jaxlint's line suppressions)
— never by silently skipping the check.
"""

from __future__ import annotations

import ast
import importlib
import inspect
import pkgutil
import re
import sys
import textwrap
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

import numpy as np

from .diagnostics import Diagnostic, Severity
from .specs import DataSpec, is_known, shape_struct, trace_element

_IGNORE_RE = re.compile(r"#\s*keystone:\s*ignore\[([A-Z0-9,\s]+)\]")

#: modules swept for Operator subclasses — importing them registers
#: every built-in node class via ``__subclasses__``.
_REGISTRY_ROOTS = (
    "keystone_tpu.nodes",
    "keystone_tpu.workflow.pipeline",
    "keystone_tpu.workflow.operators",
    "keystone_tpu.workflow.fusion_rule",
)


# ---------------------------------------------------------------- registry


def _import_registry() -> None:
    for root in _REGISTRY_ROOTS:
        mod = importlib.import_module(root)
        if hasattr(mod, "__path__"):
            for info in pkgutil.walk_packages(mod.__path__, root + "."):
                try:
                    importlib.import_module(info.name)
                except Exception:
                    pass  # an optional-dep module must not kill the sweep


def _all_subclasses(cls: type) -> Iterable[type]:
    for sub in cls.__subclasses__():
        yield sub
        yield from _all_subclasses(sub)


def operator_registry() -> List[type]:
    """Every registered Operator subclass defined inside keystone_tpu,
    deterministically ordered."""
    from ..workflow.operators import Operator

    _import_registry()
    seen: Dict[type, None] = {}
    for cls in _all_subclasses(Operator):
        if cls.__module__.startswith("keystone_tpu."):
            seen.setdefault(cls)
    return sorted(seen, key=lambda c: (c.__module__, c.__qualname__))


# ------------------------------------------------------------------ probes

#: qualname -> zero-arg factory returning (instance, element_shapes).
#: Probes exist so classes whose constructors need arguments still get
#: instance-level checks (property-valued ``fusable``, fuse-key
#: inspection, the KP502 distributivity trace). A contract-bearing
#: class without a probe falls back to class-level checks only.
def _probe_factories() -> Dict[str, Any]:
    def conv():
        from ..nodes.images.core import Convolver

        return Convolver(
            np.ones((2, 3, 3, 3), np.float32), 8, 8, 3), [(8, 8, 3)]

    def conv_rect_pool():
        from ..nodes.images.core import Convolver, _ConvRectifyPoolStage

        c = Convolver(np.ones((2, 3, 3, 3), np.float32), 8, 8, 3)
        return _ConvRectifyPoolStage(c, 0.0, 0.0, 2, 2), [(8, 8, 3)]

    def fused_chain(cls_name):
        def make():
            import keystone_tpu.nodes.util.fusion as fz
            from ..nodes.stats.normalization import SignedHellingerMapper

            return getattr(fz, cls_name)([SignedHellingerMapper()]), [(6,)]

        return make

    table = {
        "Convolver": conv,
        "_ConvRectifyPoolStage": conv_rect_pool,
        "_RectifyPoolStage": lambda: (
            _cls("keystone_tpu.nodes.images.core", "_RectifyPoolStage")(
                0.0, 0.0, 2, 2), [(8, 8, 2)]),
        "Pooler": lambda: (
            _cls("keystone_tpu.nodes.images.core", "Pooler")(2, 2),
            [(8, 8, 3)]),
        "Cropper": lambda: (
            _cls("keystone_tpu.nodes.images.core", "Cropper")(0, 0, 4, 4),
            [(8, 8, 3)]),
        "ClassLabelIndicatorsFromInt": lambda: (
            _cls("keystone_tpu.nodes.util.basic",
                 "ClassLabelIndicatorsFromInt")(4), [()]),
        "ClassLabelIndicatorsFromIntArray": lambda: (
            _cls("keystone_tpu.nodes.util.basic",
                 "ClassLabelIndicatorsFromIntArray")(4), [(3,)]),
        "ColumnSampler": lambda: (
            _cls("keystone_tpu.nodes.stats.normalization",
                 "ColumnSampler")(4), [(8, 6)]),
        "CosineRandomFeatures": lambda: (
            _cls("keystone_tpu.nodes.stats.random_features",
                 "CosineRandomFeatures")(6, 8), [(6,)]),
        "RandomSignNode": lambda: (
            _cls("keystone_tpu.nodes.stats.random_features",
                 "RandomSignNode")(6), [(6,)]),
        "StandardScalerModel": lambda: (
            _cls("keystone_tpu.nodes.stats.scalers", "StandardScalerModel")(
                np.zeros(6, np.float32), np.ones(6, np.float32)), [(6,)]),
        "LinearMapper": lambda: (
            _cls("keystone_tpu.nodes.learning.linear", "LinearMapper")(
                np.ones((6, 3), np.float32)), [(6,)]),
        "BlockLinearMapper": lambda: (
            _cls("keystone_tpu.nodes.learning.block_ls",
                 "BlockLinearMapper")(np.ones((6, 3), np.float32)), [(6,)]),
        "BlockLeastSquaresEstimator": lambda: (
            _cls("keystone_tpu.nodes.learning.block_ls",
                 "BlockLeastSquaresEstimator")(4, 1), [(6,)]),
        "MatrixVectorizer": lambda: (
            _cls("keystone_tpu.nodes.util.basic", "MatrixVectorizer")(),
            [(4, 3)]),
        "_FunctionTransformer": lambda: (
            _cls("keystone_tpu.workflow.pipeline", "_FunctionTransformer")(
                lambda x: x), [(6,)]),
        "TransformerChain": lambda: (
            _cls("keystone_tpu.workflow.pipeline", "TransformerChain")(
                [_cls("keystone_tpu.nodes.stats.normalization",
                      "SignedHellingerMapper")()]), [(6,)]),
        "FusedBatchTransformer": fused_chain("FusedBatchTransformer"),
        "MegafusedBatchTransformer": fused_chain("MegafusedBatchTransformer"),
        "GatherConcatStage": lambda: (
            _cls("keystone_tpu.nodes.util.fusion", "GatherConcatStage")(
                [_cls("keystone_tpu.nodes.stats.normalization",
                      "SignedHellingerMapper")()]), [(6,)]),
    }
    return table


def _cls(module: str, name: str) -> type:
    return getattr(importlib.import_module(module), name)


#: element shapes tried when a probe declares none.
_DEFAULT_ELEMS: Tuple[Tuple[int, ...], ...] = ((6,), (8, 8, 3))


def probe_instance(cls: type):
    """Best-effort instance of ``cls`` for instance-level checks:
    ``(instance, element_shapes)`` or ``(None, ())`` when the class
    cannot be constructed without real state."""
    factory = _probe_factories().get(cls.__name__)
    if factory is not None:
        try:
            return factory()
        except Exception:
            return None, ()
    try:
        return cls(), list(_DEFAULT_ELEMS)
    except Exception:
        return None, ()


# --------------------------------------------------------- AST utilities


_MODULE_AST_CACHE: Dict[str, Optional[ast.Module]] = {}


def _module_ast(module_name: str) -> Optional[ast.Module]:
    tree = _MODULE_AST_CACHE.get(module_name, False)
    if tree is not False:
        return tree
    tree = None
    try:
        mod = sys.modules.get(module_name) or importlib.import_module(
            module_name)
        tree = ast.parse(inspect.getsource(mod))
    except Exception:
        tree = None
    _MODULE_AST_CACHE[module_name] = tree
    return tree


def _class_ast(cls: type) -> Optional[ast.ClassDef]:
    """The class's own ``ClassDef`` node (no source → None, e.g. for
    classes built dynamically with ``type(...)``)."""
    try:
        src = textwrap.dedent(inspect.getsource(cls))
    except Exception:
        return None
    try:
        tree = ast.parse(src)
    except SyntaxError:
        return None
    for node in tree.body:
        if isinstance(node, ast.ClassDef) and node.name == cls.__name__:
            return node
    return None


def suppressed_rules(cls: type) -> frozenset:
    """Rules suppressed with ``# keystone: ignore[KP50x]`` on (or right
    above) the ``class`` line — the explicit genuine-exception channel."""
    try:
        lines, _ = inspect.getsourcelines(cls)
    except Exception:
        return frozenset()
    head = []
    for line in lines:
        head.append(line)
        if line.lstrip().startswith("class ") and line.rstrip().endswith(":"):
            break
        if len(head) > 8:
            break
    out = set()
    for line in head:
        m = _IGNORE_RE.search(line)
        if m:
            out.update(r.strip() for r in m.group(1).split(","))
    return frozenset(out)


def _jit_donations(tree: ast.Module) -> Dict[str, Tuple[Optional[tuple], int]]:
    """Module-level jitted functions: name -> (donate_argnums tuple or
    None when the decorator declares none, positional arity). Recognizes
    ``@jax.jit``/``@jit``/``@partial(jax.jit, ...)`` decorators."""
    out: Dict[str, Tuple[Optional[tuple], int]] = {}
    for fn in tree.body:
        if not isinstance(fn, ast.FunctionDef):
            continue
        for dec in fn.decorator_list:
            target = dec.func if isinstance(dec, ast.Call) else dec
            is_jit = (
                (isinstance(target, ast.Name) and target.id == "jit")
                or (isinstance(target, ast.Attribute) and target.attr == "jit")
                or (isinstance(dec, ast.Call)
                    and isinstance(dec.func, ast.Name)
                    and dec.func.id == "partial" and dec.args
                    and ((isinstance(dec.args[0], ast.Attribute)
                          and dec.args[0].attr == "jit")
                         or (isinstance(dec.args[0], ast.Name)
                             and dec.args[0].id == "jit")))
            )
            if not is_jit:
                continue
            donate: Optional[tuple] = None
            if isinstance(dec, ast.Call):
                for kw in dec.keywords:
                    if kw.arg == "donate_argnums":
                        try:
                            donate = tuple(ast.literal_eval(kw.value)) \
                                if not isinstance(kw.value, ast.Constant) \
                                else (ast.literal_eval(kw.value),)
                        except Exception:
                            donate = ()
            out[fn.name] = (donate, len(fn.args.args))
            break
    return out


def _called_names(cls_node: ast.ClassDef) -> set:
    names = set()
    for sub in ast.walk(cls_node):
        if isinstance(sub, ast.Call):
            if isinstance(sub.func, ast.Name):
                names.add(sub.func.id)
            elif isinstance(sub.func, ast.Attribute):
                names.add(sub.func.attr)
    return names


def _batch_methods(cls_node: ast.ClassDef) -> List[ast.FunctionDef]:
    return [n for n in cls_node.body
            if isinstance(n, ast.FunctionDef)
            and n.name in ("apply_batch", "batch_transform")]


def _reads_mask(cls: type) -> bool:
    """Does the class's unfused batch path read a dataset ``.mask``
    (directly, or by passing it into a module-level jitted helper)?
    Walks the MRO: an INHERITED masking batch path re-inherits the
    padded-row contract just the same."""
    for klass in cls.__mro__:
        node = _class_ast(klass)
        if node is None:
            continue
        for fn in _batch_methods(node):
            for sub in ast.walk(fn):
                if isinstance(sub, ast.Attribute) and sub.attr == "mask" \
                        and isinstance(sub.ctx, ast.Load):
                    return True
    return False


# ----------------------------------------------------------- rule checks


def _static_attr(cls: type, name: str):
    """Class attribute WITHOUT triggering properties: the raw descriptor
    for property-valued contracts, the plain value otherwise."""
    try:
        return inspect.getattr_static(cls, name)
    except AttributeError:
        return None


def _defines_fuse(cls: type) -> bool:
    return callable(getattr(cls, "fuse", None))


def _decompose(op) -> Tuple[Optional[Any], Any, Any, Optional[str]]:
    """The stage's fused-program decomposition via the SAME path the
    fusion builder uses — ``(key, params, fn, None)`` on success,
    ``(None, None, None, reason)`` when the decomposition itself fails.
    Computed once per audit and shared by KP501 (key inspection) and
    KP502 (distributivity trace)."""
    from ..nodes.util.fusion import stage_fuse

    try:
        key, params, fn = stage_fuse(op)
        return key, params, fn, None
    except Exception as e:
        return None, None, None, f"{type(e).__name__}: {e}"


def _kp501_instance(op, label: str, decomp=None,
                    vertex=None) -> List[Diagnostic]:
    from ..nodes.util.fusion import contains_opaque

    if not getattr(op, "fusable", False):
        return []
    key, _, _, err = decomp if decomp is not None else _decompose(op)
    if err is not None:
        return [Diagnostic(
            "KP501", Severity.WARNING,
            f"fusable stage's fuse() decomposition failed ({err}); fused "
            "programs containing it cannot build",
            vertex=vertex, label=label)]
    if contains_opaque(key):
        how = ("declares fusable but implements no fuse() decomposition"
               if not _defines_fuse(type(op))
               else "fuse() returns an id-keyed (opaque) component")
        return [Diagnostic(
            "KP501", Severity.WARNING,
            f"{how}: fused programs containing this stage are cached per "
            "instance and silently re-traced on every rebuilt pipeline "
            "(the PR-6 ~5x re-apply retrace class); implement a "
            "structural fuse() with params as traced arguments",
            vertex=vertex, label=label)]
    return []


def _elem_struct(shape) -> Any:
    return shape_struct(tuple(shape), np.float32)


def _kp502_instance(op, label: str, elems: Sequence[Any], decomp=None,
                    vertex=None) -> List[Diagnostic]:
    """Distributivity of the declared-chunkable batch path, proven (or
    refuted) shape-level: trace the whole-batch form and two chunk
    forms; concat of chunks must agree with the whole."""
    import jax

    if not getattr(op, "chunkable", False):
        return []
    _, params, fn, err = decomp if decomp is not None else _decompose(op)
    if err is not None:
        return []  # decomposition failure already reported by KP501

    for elem in elems:
        if not (hasattr(elem, "shape") and hasattr(elem, "dtype")):
            elem = _elem_struct(elem)
        shapes = {}
        failed = False
        for n in (3, 4, 7):
            xs = jax.ShapeDtypeStruct((n,) + tuple(elem.shape), elem.dtype)
            ms = jax.ShapeDtypeStruct((n,), np.bool_)
            try:
                out = trace_element(
                    lambda xb, mb: fn(params, xb, mb), (xs, ms))
            except Exception:
                # a shape complaint against a PROBE element only means
                # the probe guessed the wrong input shape — try the next
                # candidate; the pipeline-level pass uses real specs
                failed = True
                break
            if not is_known(out) or not (
                    hasattr(out, "shape") and hasattr(out, "dtype")):
                failed = True  # host code / pytree out: not provable
                break
            shapes[n] = (tuple(out.shape), np.dtype(out.dtype))
        if failed:
            continue
        (s3, d3), (s4, d4), (s7, d7) = shapes[3], shapes[4], shapes[7]
        # chunk outputs must concatenate into the whole-batch output:
        # identical tails/dtypes and leading axes that add up
        ok = (
            len(s3) == len(s4) == len(s7)
            and len(s3) >= 1
            and s3[1:] == s4[1:] == s7[1:]
            and d3 == d4 == d7
            and s3[0] + s4[0] == s7[0]
        )
        if not ok:
            return [Diagnostic(
                "KP502", Severity.ERROR,
                "declares chunkable but the batch path provably does not "
                f"distribute over chunks: eval_shape gives {s3}+{s4} for "
                f"chunks of 3+4 rows vs {s7} for the whole 7-row batch "
                "(f(concat(chunks)) != concat(f(chunks))); drop the "
                "chunkable declaration or make the batch path map-like "
                "in the example axis",
                vertex=vertex, label=label)]
        return []  # proven distributive on the first traceable element
    return []


def _kp503_class(cls: type) -> List[Diagnostic]:
    donates = _static_attr(cls, "donates_deps")
    if not isinstance(donates, tuple) or not donates:
        return []
    label = cls.__name__
    # walk the MRO: donates_deps resolves through inheritance, so the
    # jitted step that honors it may live in (and call into) any base
    # class's module — an empty-body subclass of an honest donor is
    # just as honest
    called: set = set()
    jitted: Dict[str, Tuple[Optional[tuple], int]] = {}
    any_source = False
    for klass in cls.__mro__:
        tree = _module_ast(klass.__module__)
        node = _class_ast(klass)
        if tree is None or node is None:
            continue
        any_source = True
        mod_jitted = _jit_donations(tree)
        jitted.update(
            {n: v for n, v in mod_jitted.items() if n not in jitted})
        called |= _called_names(node) & set(mod_jitted)
    if not any_source:
        return [Diagnostic(
            "KP503", Severity.WARNING,
            "declares donates_deps but its source is unavailable for the "
            "donate_argnums cross-check",
            label=label)]
    donated_steps = {n: jitted[n] for n in called if jitted[n][0]}
    if not called:
        return [Diagnostic(
            "KP503", Severity.WARNING,
            f"declares donates_deps={donates!r} but no jitted step is "
            "reachable from its methods; the promised buffer donation "
            "never happens (and KP301 restricts the producer's consumers "
            "for nothing)",
            label=label)]
    if not donated_steps:
        return [Diagnostic(
            "KP503", Severity.WARNING,
            f"declares donates_deps={donates!r} but none of its jitted "
            f"steps ({', '.join(sorted(called))}) carries donate_argnums; "
            "the dependency buffer is never actually donated",
            label=label)]
    bad = [
        f"{name}: donate_argnums={dn} exceeds its {arity} parameter(s)"
        for name, (dn, arity) in donated_steps.items()
        if any(i >= arity for i in dn)
    ]
    if bad:
        return [Diagnostic(
            "KP503", Severity.WARNING,
            "donate_argnums is mis-indexed against the step signature: "
            + "; ".join(sorted(bad)),
            label=label)]
    return []


def _kp504_class(cls: type) -> List[Diagnostic]:
    if not isinstance(_static_attr(cls, "fusable"), bool) \
            or not cls.fusable:
        # property-valued fusable classes are checked per instance
        if not isinstance(getattr(cls, "fusable", False), property):
            return []
    if bool(_static_attr(cls, "fuse_masks_output")):
        return []
    if not _reads_mask(cls):
        return []
    return [Diagnostic(
        "KP504", Severity.ERROR,
        "the unfused batch path masks padded rows (reads the dataset "
        "mask) but the class declares no fuse_masks_output — inside a "
        "fused program padded rows would stop being re-zeroed and "
        "mask-less reductions downstream would read corrupt values "
        "(the padded-row class PR 4's review caught by hand)",
        label=cls.__name__)]


def _mask_aware_fuse(op) -> bool:
    """A fuse() decomposition carrying the mask-aware sentinel threads
    the padded-row mask through its inner stages by construction — it
    cannot corrupt padded rows, so KP504 does not apply (the fusion
    machinery classes: FusedBatchTransformer, GatherConcatStage)."""
    f = getattr(op, "fuse", None)
    if f is None:
        return False
    try:
        from ..nodes.util.fusion import MASK_AWARE

        res = f()
        return len(res) == 4 and res[3] == MASK_AWARE
    except Exception:
        return False


def _fit_return_classes(cls: type) -> List[type]:
    """Classes constructed in ``fit``/``fit_datasets`` return statements,
    resolved against the defining module's namespace — the static answer
    to 'what transformer does this estimator produce?'."""
    node = _class_ast(cls)
    if node is None:
        return []
    mod = sys.modules.get(cls.__module__)
    ns = vars(mod) if mod is not None else {}
    out: List[type] = []
    for fn in node.body:
        if not isinstance(fn, ast.FunctionDef) \
                or fn.name not in ("fit", "fit_datasets"):
            continue
        for sub in ast.walk(fn):
            if not (isinstance(sub, ast.Return)
                    and isinstance(sub.value, ast.Call)):
                continue
            f = sub.value.func
            name = f.id if isinstance(f, ast.Name) else (
                f.attr if isinstance(f, ast.Attribute) else None)
            got = ns.get(name)
            if isinstance(got, type):
                out.append(got)
    return out


def _kp501_estimator_class(cls: type) -> List[Diagnostic]:
    """``fusable_fit`` promises the fit yields a traceable transformer;
    the fitted class must therefore carry a structural fuse() or every
    fused chain absorbing this boundary re-traces per instance."""
    from ..workflow.operators import Operator

    if not bool(_static_attr(cls, "fusable_fit")):
        return []
    diags: List[Diagnostic] = []
    for fitted in _fit_return_classes(cls):
        if not (isinstance(fitted, type) and issubclass(fitted, Operator)):
            continue
        fus = _static_attr(fitted, "fusable")
        declared = (isinstance(fus, property)
                    or (isinstance(fus, bool) and fus))
        if declared and not _defines_fuse(fitted):
            diags.append(Diagnostic(
                "KP501", Severity.WARNING,
                f"fusable_fit promises a traceable fit, but the fitted "
                f"class {fitted.__name__} declares fusable without a "
                "structural fuse() — fused chains crossing this "
                "estimator boundary get id-keyed programs and re-trace "
                "on every re-apply",
                label=cls.__name__))
    return diags


# ------------------------------------------------------------- audit API


def audit_operator(op, elems: Sequence[Any] = (),
                   vertex=None) -> List[Diagnostic]:
    """Instance-level contract audit of one operator: KP501 (fuse-key
    inspection), KP502 (distributivity trace over ``elems``), and the
    class-level KP503/KP504 AST cross-checks. Honors the class-line
    ``# keystone: ignore[KP50x]`` suppression."""
    cls = type(op)
    label = getattr(op, "label", cls.__name__)
    decomp = _decompose(op)
    diags: List[Diagnostic] = []
    diags.extend(_kp501_instance(op, label, decomp, vertex=vertex))
    if elems:
        diags.extend(_kp502_instance(op, label, elems, decomp,
                                     vertex=vertex))
    kp504 = _kp504_class(cls)
    if kp504 and _mask_aware_fuse(op):
        kp504 = []
    for d in _kp503_class(cls) + kp504 + _kp501_estimator_class(cls):
        diags.append(Diagnostic(d.rule, d.severity, d.message,
                                vertex=vertex, label=label))
    sup = suppressed_rules(cls)
    return [d for d in diags if d.rule not in sup]


def audit_class(cls: type) -> Tuple[List[Diagnostic], bool]:
    """Registry-side audit of one operator class. Returns
    ``(diagnostics, probed)`` — ``probed`` False means only the
    class-level (AST) checks could run."""
    op, elems = probe_instance(cls)
    diags: List[Diagnostic] = []
    if op is not None:
        decomp = _decompose(op)
        diags.extend(_kp501_instance(op, cls.__name__, decomp))
        diags.extend(_kp502_instance(op, cls.__name__, elems, decomp))
    else:
        fus = _static_attr(cls, "fusable")
        if isinstance(fus, bool) and fus and not _defines_fuse(cls):
            diags.extend(_kp501_instance_classlevel(cls))
    diags.extend(_kp503_class(cls))
    kp504 = _kp504_class(cls)
    if kp504 and op is not None and _mask_aware_fuse(op):
        kp504 = []
    diags.extend(kp504)
    diags.extend(_kp501_estimator_class(cls))
    sup = suppressed_rules(cls)
    return [d for d in diags if d.rule not in sup], op is not None


def _kp501_instance_classlevel(cls: type) -> List[Diagnostic]:
    return [Diagnostic(
        "KP501", Severity.WARNING,
        "declares fusable but implements no fuse() decomposition: fused "
        "programs containing this stage are cached per instance and "
        "silently re-traced on every rebuilt pipeline (the PR-6 ~5x "
        "re-apply retrace class)",
        label=cls.__name__)]


def audit_registry() -> Tuple[List[Tuple[type, Diagnostic]], Dict[str, int]]:
    """Sweep every registered Operator/Estimator subclass. Returns the
    per-class findings plus sweep statistics."""
    findings: List[Tuple[type, Diagnostic]] = []
    probed = 0
    classes = operator_registry()
    for cls in classes:
        diags, was_probed = audit_class(cls)
        probed += bool(was_probed)
        findings.extend((cls, d) for d in diags)
    return findings, {"classes": len(classes), "probed": probed}


# ------------------------------------------------------------ graph pass


def _input_elems(graph, node, specs) -> List[Any]:
    """Known dataset element specs feeding this node — the KP502 trace
    runs against the pipeline's REAL propagated shapes when available."""
    elems = []
    for d in graph.get_dependencies(node):
        s = specs.get(d)
        if isinstance(s, DataSpec) and is_known(s.element) \
                and hasattr(s.element, "shape"):
            elems.append(s.element)
    return elems[:1]


def contract_pass(graph, specs: Optional[Dict] = None) -> List[Diagnostic]:
    """KP5xx contract audit over every operator instance in a lowered
    graph (the ``validate(level="full")`` surface). Input element specs
    come from the analyzer's propagation, so the KP502 distributivity
    trace uses the pipeline's actual shapes."""
    from .propagate import _label

    specs = specs or {}
    diags: List[Diagnostic] = []
    for node in sorted(graph.operators, key=lambda n: n.id):
        op = graph.get_operator(node)
        try:
            diags.extend(audit_operator(
                op, _input_elems(graph, node, specs), vertex=node))
        except Exception:
            continue  # the audit must never break validation
    # one finding per (rule, anchor): composite operators can repeat
    seen = set()
    out = []
    for d in diags:
        k = (d.rule, d.anchor, d.message)
        if k not in seen:
            seen.add(k)
            out.append(d)
    return out
