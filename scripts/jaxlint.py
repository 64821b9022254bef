#!/usr/bin/env python
"""jaxlint — repo-specific static JAX lints for keystone_tpu.

Pure-AST (no imports of the linted code, no jax required), so it runs in
milliseconds as a pre-test gate (`scripts/lint.sh`) and as a tier-1
pytest (tests/test_jaxlint.py). Rules encode project discipline the type
system cannot (see ANALYSIS.md for the full catalog):

  KJ001  jnp-loop-accumulation (under ``nodes/``): a raw ``jnp.*`` call
         feeding a loop-carried accumulation inside a Python for/while.
         Each iteration dispatches its own XLA program and the loop-
         carried value forces a dependency chain — use `lax.scan`/
         `lax.fori_loop`, or a jitted step function (the donated-buffer
         epoch pattern in nodes/learning).
  KJ002  numpy-inside-jit: a ``np.*``/``numpy.*`` *call* in the body of
         a ``jax.jit``-decorated function. NumPy calls on tracers either
         crash (TracerArrayConversionError) or silently constant-fold at
         trace time. Attribute reads (``np.float32``, ``np.pi``) are
         fine — only calls are flagged.
  KJ003  missing-donate (under ``nodes/learning/``): a jitted function
         named ``*_step``/``*_epoch``/``*_sweep`` — the solver-loop
         naming convention for steps that rebuild O(model)-sized state —
         must declare ``donate_argnums`` so XLA reuses the state buffers
         instead of allocating fresh HBM every iteration.
  KJ004  wall-clock-duration: a ``time.time()`` call inside
         ``keystone_tpu/``. Wall-clock is NTP-steppable and coarse;
         every duration measurement (profiler, telemetry spans, stall
         histograms) must use ``time.perf_counter()``. Genuine
         wall-clock timestamps (trace epoch anchors, file-mtime
         comparisons) suppress with the standard comment.
  KJ005  blocking-host-pull (under ``workflow/`` and ``nodes/``): a
         ``.block_until_ready()`` call, or ``np.asarray(...)`` over a
         device value (a ``jnp.*`` call result, or a dataset payload
         attribute ``.array``/``.data``), in a hot path. Both serialize
         the async dispatch queue. Pulls that must happen route through
         ``data.dataset.sync_pull`` (one-element transfer: a value that
         has reached the host is an honest fence anywhere) or
         ``Dataset.sync()``; sanctioned drains (the overlap engine's
         in-order result pulls) carry the suppression comment.

  KJ006  fresh-jit-per-call (under ``workflow/`` and ``nodes/``):
         ``jax.jit`` applied to a freshly constructed closure or lambda
         inside a loop or per-call scope. jit caches by function-object
         identity, so each call constructs a new callable, misses the
         cache, and silently re-traces + recompiles — the exact compile
         tax the compile-bounded execution work (ISSUE 5) eliminates.
         Cache the jitted fn at module level, on the instance
         (``self.__dict__['_jitted']``), or in an explicit program
         cache keyed on structure (``nodes/util/fusion``).
  KJ007  scan-carry-realloc (under ``workflow/`` and ``nodes/``): a
         ``lax.scan``/``lax.fori_loop`` body that rebuilds a carried
         buffer with an allocating/copying jnp call (``concatenate``,
         ``stack``, ``pad``, ``tile``, ...) and no in-place update
         pattern. XLA donates the scan carry between trips ONLY when
         the body updates it in place (``lax.dynamic_update_slice``,
         ``.at[...].set``) — a grow/copy carry silently doubles
         O(model) state every trip, exactly what the megafused
         single-program apply path must never do. Scan-invariant model
         state belongs in the closure, not the carry.

  KJ008  hot-path-state-write (under ``workflow/`` and ``nodes/``): an
         assignment to ``self.*`` or a module global — or an in-place
         mutation of a module-level container — inside an operator's
         ``apply``/``apply_batch``/``_chunk_loop``. The concurrent DAG
         scheduler (PR 4, default on) may force two vertices
         simultaneously, making the write interleaving schedule-
         dependent (the KP511 race class, see
         ``keystone_tpu/analysis/effects.py`` for the graph-level
         pass). The ``self.__dict__[...]`` instance-memo idiom and
         module-level structure-keyed caches (``*CACHE*``/``*PENDING*``
         names) are sanctioned.

  KJ009  hard-coded-mesh-axis / bare-device-put: a bare ``"data"`` /
         ``"model"`` string literal used as a mesh axis name in a
         sharding construction or collective call under ``nodes/`` /
         ``workflow/`` (the canonical names live in
         ``parallel/mesh.py`` — import ``DATA_AXIS``/``MODEL_AXIS`` so
         a mesh relayout stays a one-place change), and — under
         ``parallel/`` / ``data/`` — ``jax.device_put`` without an
         explicit sharding/device argument (defaults to device 0,
         silently un-sharding whatever flows through a mesh hot path).

  KJ010  output-layout-leak (under ``workflow/`` and ``nodes/``): a
         ``jax.jit``/``pjit`` call passing ``in_shardings`` but
         omitting ``out_shardings``. Pinning only the input layout
         leaves the OUTPUT layout to XLA's partitioner — the caller
         gets whatever placement compilation happened to pick, and the
         next stage pays an unpriced reshard to recover the layout the
         plan expected (exactly the implicit boundary move KP601 lints
         and the sharding planner prices). A jit that constrains its
         inputs must say where its outputs land.

  KJ011  literal-precision-cast (under ``workflow/`` and ``nodes/``):
         a literal ``jnp.float32(...)`` / ``.astype(jnp.float32)`` /
         ``asarray(..., jnp.float32)`` inside a ``fuse()``,
         ``_chunk_loop``, or ``_build_program`` body. Fused-program
         code runs under the
         mixed-precision policy pass (analysis/precision.py): a pinned
         f32 cast — or an f32 scalar param, which jnp promotion
         silently widens a bf16 tensor against — re-promotes a halved
         boundary back to f32 and defeats the policy without any
         diagnostic. Match the input dtype
         (``jnp.asarray(c, x.dtype)``) instead; genuine kernel
         constraints (RFFT accepts only f32/f64, uint8 pixel decode)
         carry an explicit suppression.

  KJ012  dynamic-metric-name (under ``workflow/`` and ``nodes/``):
         ``telemetry.counter/gauge/histogram(...)`` called with a
         non-literal name (f-string, ``%``/``+`` formatting,
         ``.format()``, or a variable) in hot-path code. The metrics
         registry is process-wide and created-on-first-use: a name
         formatted per vertex/label/chunk mints a NEW counter per
         distinct value — unbounded cardinality that grows the
         registry (and every trace's embedded snapshot) for the life
         of the process. Use one literal name and carry the dimension
         in a span arg instead; the sanctioned low-cardinality case
         (per-process ``dispatch.*.p<i>`` accounting) lives in
         ``telemetry/instrument.py``, outside this rule's scope, and
         any genuine in-scope exception carries a suppression.

  KJ013  transpose-then-reshape (under ``workflow/`` and ``nodes/``): a
         ``.reshape(...)`` whose receiver (or ``jnp.reshape`` whose
         argument) contains a transpose — ``.T``/``.mT``,
         ``transpose(...)``, ``swapaxes``/``moveaxis`` — inside a
         ``fuse()``, ``_chunk_loop``, or ``_build_program`` body. A
         transpose feeding a reshape cannot stay a free layout
         relabeling: XLA must materialize the permuted buffer before
         re-flattening it, so the fused program pays a full
         write+read of the tensor that the roofline's boundary-bytes
         model (analysis/roofline.py) cannot see — the in-body twin of
         the KP802 movement-dominance lint. Reorder the computation
         (reshape first, or keep the axis order end-to-end); genuine
         layout contracts (kernel-required NHWC flips) carry a
         suppression with the rationale.

  KJ014  blocking-host-io (under ``workflow/`` and ``nodes/``):
         ``time.sleep(...)``, blocking file reads (``open(...)`` /
         ``Path.read_text/read_bytes``), or network calls
         (``urllib.request.urlopen``, ``requests.get/post/...``,
         ``socket.create_connection``) inside an operator hot-path
         method (``apply``/``apply_batch``/``_chunk_loop``/...). The
         KJ005 companion for non-device blocking: a host stall on the
         apply path gates EVERY request behind the full I/O latency,
         is invisible to the roofline's time model, and busts the
         KP903 serving latency bound without any static trace of why.
         Hoist the I/O to construction or fit time (weights, vocab
         files), or pre-load at the serving ingress; a genuinely
         per-request external lookup carries a suppression naming why
         it cannot be batched ahead of the request.

  KJ015  manual-chunk-knob (under ``workflow/`` and ``nodes/``): a
         direct ``.chunk_size`` config-attribute read or a
         ``KEYSTONE_CHUNK_SIZE`` environment read outside the
         sanctioned resolution sites. The chunk size is an OPTIMIZER
         decision since PR 15: the unified planner's chosen chunk
         flows through ``workflow.env.resolved_chunk_size`` into the
         host batcher (``utils/batching.py``) and the KP2xx/KP8xx
         models (``analysis/memory.resolve_chunk_rows``) from one
         place. A hot-path module reading the raw knob bypasses the
         planner's decision — the analyzer then models a chunking the
         runtime doesn't execute. Call ``resolved_chunk_size()`` (or
         take an explicit parameter) instead; the config definition
         site (``workflow/env.py``) is sanctioned by path.

  KJ016  pallas-call-outside-ops (everywhere except ``ops/``): a
         ``pl.pallas_call`` (or bare ``pallas_call``) invocation in a
         module outside ``keystone_tpu/ops/``. Kernels live in one
         place so the chain-kernel audit (scripts/lint.sh), the
         interpret-mode test oracles, the live-chip canary
         (scripts/kernel_live_check.py), and the
         ``KEYSTONE_CHAIN_KERNELS`` kill switch cover every kernel the
         runtime can dispatch. A pallas_call minted elsewhere dodges
         all four: no ``*_reference`` oracle, no canary record, no
         gate. Move the kernel into ``ops/`` (with its pure-jnp
         reference) and call the builder, or suppress with a rationale
         naming why this one cannot live there.

  KJ017  hard-coded-kernel-geometry (``ops/`` only): a literal VMEM
         byte budget (a ``<< 20`` MiB shift or a >=1 MiB integer
         constant) outside the one sanctioned definition site
         (``chain_kernels._VMEM_BUDGET``), or a literal leading
         block-row count baked into a ``pl.BlockSpec`` shape. The
         KP1003 static VMEM proof and `chain_feasible`'s runtime
         chooser share ONE working-set formula
         (``chain_kernels.chain_vmem_bytes`` /
         ``chain_block_rows``) precisely so the verifier's verdict
         and the dispatched geometry can never diverge; an inline
         byte cap or a pinned block size reintroduces a second,
         unverified arithmetic the static tier cannot see. Route the
         geometry through the shared chooser, or suppress with a
         rationale naming the kernel-specific working set.

  KJ018  trace-time-telemetry (under ``workflow/`` and ``nodes/``):
         a span or metric emission (``span(...)``, ``counter/gauge/
         histogram(...).inc/observe/...``) lexically inside a fused-
         program body — a ``fuse()``/``_chunk_loop`` body, or a
         nested closure of ``_build_program`` (its host prologue is
         build-time code; only the traced ``chunk_fn``/``per_shard``
         closures become program body). Those bodies execute at TRACE
         time: the emission fires once per compile, not once per run,
         so the recorded "latency" is trace-time, live percentile
         sketches ingest garbage, and re-runs of the warm program
         emit nothing at all. Instrument at the dispatch boundary
         (the executor / instrument layer) instead, or suppress with
         a rationale naming why the call is host-side.

  KJ019  unbounded-request-buffer (under ``serving/`` and
         ``workflow/``): a ``queue.Queue()`` (or LifoQueue/
         PriorityQueue) constructed with no maxsize — or a literal
         maxsize ≤ 0, which the stdlib treats as infinite — and, under
         ``serving/`` only, a ``SimpleQueue()`` (unbounded by
         construction) or a bare ``list.append`` onto a receiver named
         like a request buffer (queue/pending/requests/backlog/inbox/
         buffer). Every serving queue must be BOUNDED: a full queue is
         the load-shed signal (`serving.shed_total` + a flight dump),
         so an unbounded buffer silently converts overload into
         unbounded memory growth and unbounded queueing delay — the
         p99 dies long before the OOM does. Size the queue from
         ``execution_config().serving_queue_depth`` (the
         ``KEYSTONE_SERVING_QUEUE_DEPTH`` knob), or suppress with a
         rationale naming why the producer is statically bounded.

  KJ020  ooc-whole-dataset-drain (under ``data/`` and ``workflow/``): a
         whole-dataset materialization of an out-of-core source — a
         name bound from ``OutOfCoreDataset(...)``,
         ``SpilledDataset(...)``, or an ``out_of_core_*``/
         ``synthetic_out_of_core`` loader fed to ``np.asarray``/
         ``np.array``/``np.stack``/``np.concatenate`` or drained via
         ``list()``/``tuple()``. The entire point of the spill tier is
         bounded device residency through the windowed prefetcher
         (``window_iter()``/``map_windowed()``); an ad-hoc full drain
         reintroduces the dataset-sized allocation the planner promised
         away. The sanctioned full drains are the methods the classes
         themselves expose (``materialize()``/``rehydrate()``/
         ``numpy()``) at call sites that own that decision — suppress
         with a rationale when a full drain is genuinely intended.

Suppression: append ``# keystone: ignore[KJ001]`` (comma-separate for
several rules) to the flagged line, or to the ``def`` line for KJ003.

Usage: python scripts/jaxlint.py [--list-rules] [--json] [paths...]
Exit code 1 when findings remain. ``--json`` emits machine-readable
findings for CI annotation.
"""

from __future__ import annotations

import argparse
import ast
import re
import sys
from pathlib import Path
from typing import Iterator, List, NamedTuple, Optional, Set

RULES = {
    "KJ001": "raw jnp.* call in a Python-loop accumulation (use lax.scan "
             "or a jitted step fn)",
    "KJ002": "numpy call inside a jax.jit-decorated function",
    "KJ003": "jitted solver step mutating O(model) state lacks "
             "donate_argnums",
    "KJ004": "time.time() used where a duration is measured (use "
             "time.perf_counter())",
    "KJ005": "blocking host pull on a device value in a hot path "
             "(route through data.dataset.sync_pull / Dataset.sync)",
    "KJ006": "jax.jit of a freshly constructed closure/lambda in a loop "
             "or per-call scope (recompiles every call; cache the "
             "jitted fn)",
    "KJ007": "lax.scan/fori_loop carry rebuilt by an allocating jnp call "
             "with no in-place update (dynamic_update_slice / .at[].set) "
             "— the carry buffer reallocates O(model) state every trip",
    "KJ008": "state write in an operator hot path: assignment to self.* "
             "or a module global inside apply/apply_batch/_chunk_loop — "
             "the concurrent scheduler may force two such vertices "
             "simultaneously (use the self.__dict__ memo idiom or a "
             "structure-keyed cache)",
    "KJ009": "hard-coded mesh axis name ('data'/'model') in a sharding or "
             "collective call (use meshlib.DATA_AXIS/MODEL_AXIS), or a "
             "jax.device_put without an explicit sharding in a "
             "parallel-adjacent hot path (placement must be deliberate "
             "on a mesh)",
    "KJ010": "jax.jit/pjit with in_shardings but no out_shardings: the "
             "output layout leaks to XLA's partitioner and the caller "
             "re-shards downstream (declare out_shardings so the "
             "boundary layout is a decision, not an accident)",
    "KJ011": "literal float32 cast inside a fuse()/_chunk_loop body: a "
             "pinned jnp.float32/astype(jnp.float32) in fused-program "
             "code silently promotes bf16 boundaries back to f32 and "
             "defeats any precision policy (match the input dtype, or "
             "suppress with a kernel-constraint rationale)",
    "KJ012": "telemetry counter/gauge/histogram called with a "
             "dynamically formatted name in a hot path: the registry "
             "is process-wide and created-on-first-use, so a per-"
             "vertex/label name mints unbounded metric cardinality "
             "(use one literal name; carry the dimension in a span "
             "arg)",
    "KJ013": "transpose-then-reshape chain inside a fused-program body "
             "(fuse()/_chunk_loop/_build_program): the permuted buffer "
             "must materialize before the reshape, a full write+read "
             "the roofline's boundary-bytes model cannot see — reorder "
             "the computation or keep the axis order end-to-end",
    "KJ014": "blocking host I/O in an operator hot path: time.sleep, "
             "file reads (open/Path.read_*), or network calls "
             "(urllib/requests/socket) inside apply/apply_batch/"
             "_chunk_loop stall every request for the full host-call "
             "latency — the non-device twin of KJ005 (hoist the I/O to "
             "construction/fit time, or pre-load at ingress)",
    "KJ015": "manual chunk knob: a direct config .chunk_size read or a "
             "KEYSTONE_CHUNK_SIZE env read outside the sanctioned "
             "batcher/memory-model resolution sites bypasses the "
             "unified planner's chunk decision (read "
             "workflow.env.resolved_chunk_size() instead)",
    "KJ016": "pallas_call outside keystone_tpu/ops/: kernels live in "
             "one audited home so the chain-kernel audit, the "
             "interpret-mode oracles, the live-chip canary, and the "
             "KEYSTONE_CHAIN_KERNELS kill switch cover every kernel "
             "the runtime can dispatch — move the kernel (and its "
             "pure-jnp reference) into ops/ and call the builder",
    "KJ017": "hard-coded kernel geometry in ops/: a literal VMEM byte "
             "budget outside chain_kernels._VMEM_BUDGET, or a literal "
             "leading block-row count in a pl.BlockSpec shape — the "
             "static KP1003 proof and the runtime chooser share one "
             "formula (chain_vmem_bytes/chain_block_rows); inline "
             "byte caps and pinned block sizes dodge it",
    "KJ018": "span/metric emission inside a fused-program body "
             "(fuse()/_chunk_loop, or a _build_program closure): the "
             "body runs at trace time, so the emission records "
             "compile-time not run-time and corrupts live latency "
             "percentiles — instrument at the dispatch boundary",
    "KJ019": "unbounded request buffer in a serving hot path: a "
             "queue.Queue() with no (or a non-positive literal) "
             "maxsize, a SimpleQueue, or a bare list-append request "
             "buffer — a full BOUNDED queue is the load-shed signal; "
             "an unbounded one converts overload into unbounded "
             "memory and queueing delay (size it from "
             "serving_queue_depth)",
    "KJ020": "whole-dataset drain of an out-of-core source: an "
             "OutOfCoreDataset/SpilledDataset-bound name fed to "
             "np.asarray/np.array/np.stack/np.concatenate or "
             "list()/tuple() — stream it through "
             "window_iter()/map_windowed() (or call the class's own "
             "materialize()/rehydrate() where a full drain is the "
             "sanctioned decision)",
}

_IGNORE_RE = re.compile(r"#\s*keystone:\s*ignore\[([A-Z0-9,\s]+)\]")

#: numpy module aliases recognized in Attribute roots.
_NUMPY_NAMES = {"np", "numpy", "onp"}
_JNP_NAMES = {"jnp"}
#: names whose calls are harmless inside jit (dtype casts of constants).
_NUMPY_CALL_ALLOWLIST = {"dtype"}
#: jnp attrs that are scalar casts / wrappers, not compute — a loop that
#: only casts its chunk counters while accumulating through a *jitted*
#: step function is the approved donated-buffer pattern, not a smell.
_JNP_CAST_ALLOWLIST = {
    "asarray", "array", "int8", "int16", "int32", "int64", "uint8",
    "uint16", "uint32", "uint64", "float16", "float32", "float64",
    "bfloat16", "bool_", "dtype",
}
_STEP_NAME_RE = re.compile(r"_(step|epoch|sweep)$")


class Finding(NamedTuple):
    path: str
    line: int
    rule: str
    message: str

    def __str__(self) -> str:
        return f"{self.path}:{self.line}: {self.rule} {self.message}"


def _attr_root(node: ast.AST) -> Optional[str]:
    """Root name of an attribute chain: ``np.linalg.svd`` → ``np``."""
    while isinstance(node, ast.Attribute):
        node = node.value
    return node.id if isinstance(node, ast.Name) else None


def _calls_rooted_at(
    tree: ast.AST, roots: Set[str], skip_attrs: Set[str] = frozenset()
) -> Iterator[ast.Call]:
    for sub in ast.walk(tree):
        if isinstance(sub, ast.Call) and isinstance(sub.func, ast.Attribute):
            if _attr_root(sub.func) in roots \
                    and sub.func.attr not in skip_attrs:
                yield sub


def _names_loaded(tree: ast.AST) -> Set[str]:
    return {
        n.id for n in ast.walk(tree)
        if isinstance(n, ast.Name) and isinstance(n.ctx, ast.Load)
    }


def _jit_decorator(fn: ast.FunctionDef) -> Optional[ast.AST]:
    """The decorator node if ``fn`` is jitted: ``@jax.jit``, ``@jit``,
    ``@jax.jit(...)``, or ``@partial(jax.jit, ...)``."""
    for dec in fn.decorator_list:
        target = dec.func if isinstance(dec, ast.Call) else dec
        if isinstance(target, ast.Name) and target.id == "jit":
            return dec
        if isinstance(target, ast.Attribute) and target.attr == "jit" \
                and _attr_root(target) == "jax":
            return dec
        if isinstance(dec, ast.Call) and isinstance(dec.func, ast.Name) \
                and dec.func.id == "partial" and dec.args:
            inner = dec.args[0]
            if isinstance(inner, ast.Attribute) and inner.attr == "jit" \
                    and _attr_root(inner) == "jax":
                return dec
            if isinstance(inner, ast.Name) and inner.id == "jit":
                return dec
    return None


def _decorator_kwargs(dec: ast.AST) -> Set[str]:
    if isinstance(dec, ast.Call):
        return {kw.arg for kw in dec.keywords if kw.arg}
    return set()


# ---------------------------------------------------------------- rules


def _check_loop_accumulation(tree: ast.AST, path: str) -> Iterator[Finding]:
    """KJ001: inside for/while bodies, flag (a) augmented assignment
    whose value calls jnp directly, (b) ``x = f(x, ...jnp call...)``
    self-assignment with a direct jnp call, (c) ``list.append(<jnp
    call>)`` — all loop-carried per-iteration XLA dispatch patterns."""
    for loop in ast.walk(tree):
        if not isinstance(loop, (ast.For, ast.While)):
            continue
        for stmt in loop.body:
            for sub in ast.walk(stmt):
                if isinstance(sub, ast.AugAssign):
                    if any(True for _ in _calls_rooted_at(sub.value, _JNP_NAMES, _JNP_CAST_ALLOWLIST)):
                        yield Finding(
                            path, sub.lineno, "KJ001",
                            "augmented assignment accumulates a jnp result "
                            "inside a Python loop")
                elif isinstance(sub, ast.Assign) and len(sub.targets) == 1 \
                        and isinstance(sub.targets[0], ast.Name):
                    t = sub.targets[0].id
                    if t in _names_loaded(sub.value) and any(
                            True for _ in _calls_rooted_at(sub.value, _JNP_NAMES, _JNP_CAST_ALLOWLIST)):
                        yield Finding(
                            path, sub.lineno, "KJ001",
                            f"`{t}` is rebuilt from itself with a raw jnp "
                            "call each iteration")
                elif isinstance(sub, ast.Expr) and isinstance(sub.value, ast.Call):
                    call = sub.value
                    if isinstance(call.func, ast.Attribute) \
                            and call.func.attr == "append" and call.args:
                        if any(True for _ in _calls_rooted_at(
                                call.args[0], _JNP_NAMES, _JNP_CAST_ALLOWLIST)):
                            yield Finding(
                                path, sub.lineno, "KJ001",
                                "appending a per-iteration jnp result; "
                                "each append dispatches its own program")


def _check_numpy_in_jit(tree: ast.AST, path: str) -> Iterator[Finding]:
    for fn in ast.walk(tree):
        if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        if _jit_decorator(fn) is None:
            continue
        for call in _calls_rooted_at(fn, _NUMPY_NAMES):
            func = call.func
            if isinstance(func, ast.Attribute) \
                    and func.attr in _NUMPY_CALL_ALLOWLIST:
                continue
            yield Finding(
                path, call.lineno, "KJ002",
                f"numpy call `{ast.unparse(func)}` inside jitted "
                f"`{fn.name}` — constant-folds at trace time or crashes "
                "on tracers")


def _check_wall_clock_duration(tree: ast.AST, path: str) -> Iterator[Finding]:
    """KJ004: `time.time()` calls (module-attribute form, plus the bare
    `time()` form when the file does `from time import time`). Anything
    timing-shaped in keystone_tpu/ must use the monotonic
    `time.perf_counter()`; real wall-clock timestamps are rare enough to
    carry an explicit suppression."""
    bare_time_imported = any(
        isinstance(n, ast.ImportFrom) and n.module == "time"
        and any(a.name == "time" and (a.asname or a.name) == "time"
                for a in n.names)
        for n in ast.walk(tree)
    )
    for sub in ast.walk(tree):
        if not isinstance(sub, ast.Call):
            continue
        func = sub.func
        hit = (
            isinstance(func, ast.Attribute) and func.attr == "time"
            and isinstance(func.value, ast.Name) and func.value.id == "time"
        ) or (
            bare_time_imported
            and isinstance(func, ast.Name) and func.id == "time"
        )
        if hit:
            yield Finding(
                path, sub.lineno, "KJ004",
                "time.time() is wall-clock (steppable, coarse); durations "
                "must use time.perf_counter()")


#: dataset-payload attribute names whose np.asarray() is a device pull.
_DEVICE_PAYLOAD_ATTRS = {"array", "data"}


def _check_blocking_host_pull(tree: ast.AST, path: str) -> Iterator[Finding]:
    """KJ005: `.block_until_ready()` anywhere (it serializes
    dispatch), and `np.asarray(...)` whose
    argument is provably device-resident — a direct ``jnp.*`` call
    result or a dataset payload attribute (``.array`` / ``.data``).
    Heuristic by design: a plain ``np.asarray(x)`` over host items stays
    legal, while the two patterns that reliably mean "pull a device
    value mid-pipeline" are flagged."""

    def _device_arg(node: ast.AST) -> bool:
        for sub in ast.walk(node):
            if isinstance(sub, ast.Call) and isinstance(sub.func, ast.Attribute) \
                    and _attr_root(sub.func) in _JNP_NAMES:
                return True
            if isinstance(sub, ast.Attribute) \
                    and sub.attr in _DEVICE_PAYLOAD_ATTRS \
                    and isinstance(sub.ctx, ast.Load):
                return True
        return False

    for sub in ast.walk(tree):
        if not isinstance(sub, ast.Call):
            continue
        func = sub.func
        if isinstance(func, ast.Attribute) and func.attr == "block_until_ready":
            yield Finding(
                path, sub.lineno, "KJ005",
                "block_until_ready() serializes async dispatch in a hot "
                "path; where a fence is needed, use the library's one "
                "idiom, data.dataset.sync_pull / Dataset.sync()")
        elif isinstance(func, ast.Attribute) and func.attr == "asarray" \
                and _attr_root(func) in _NUMPY_NAMES and sub.args \
                and _device_arg(sub.args[0]):
            yield Finding(
                path, sub.lineno, "KJ005",
                "np.asarray over a device value blocks the dispatch "
                "queue mid-pipeline; pull through data.dataset.sync_pull "
                "or defer to the overlap engine's in-order drain")


def _is_jit_call(func: ast.AST) -> bool:
    """``jax.jit(...)`` / ``jit(...)`` as a CALL (decorators live in
    decorator_list and are evaluated once at def time — not flagged)."""
    if isinstance(func, ast.Name):
        return func.id == "jit"
    return (isinstance(func, ast.Attribute) and func.attr == "jit"
            and _attr_root(func) == "jax")


def _check_fresh_jit(tree: ast.AST, path: str) -> Iterator[Finding]:
    """KJ006: jit caches compiled executables by FUNCTION OBJECT
    identity, so ``jax.jit`` over a freshly constructed callable — a
    lambda, or a function defined in the same (per-call) scope — misses
    that cache on every call and silently re-traces + recompiles each
    time. Two patterns are flagged in ``workflow/``/``nodes/``:

      (a) any ``jax.jit(...)`` call inside a ``for``/``while`` body —
          one compile per iteration, the worst case;
      (b) ``jax.jit(<lambda or same-scope def>)`` inside a function
          body — one compile per CALL of the enclosing function.

    The sanctioned fixes are module-level jits, instance-memoized jits
    (the ``self.__dict__['_jitted']`` idiom — its argument is a call
    expression, so it is not flagged), or an explicit program cache
    (``nodes/util/fusion._PROGRAM_CACHE``, which suppresses)."""
    # (a) jit calls under a loop
    for loop in ast.walk(tree):
        if not isinstance(loop, (ast.For, ast.While)):
            continue
        for sub in ast.walk(loop):
            if isinstance(sub, ast.Call) and _is_jit_call(sub.func):
                yield Finding(
                    path, sub.lineno, "KJ006",
                    "jax.jit inside a loop body compiles a fresh program "
                    "every iteration; hoist and cache the jitted fn")

    # (b) jit of a lambda / same-scope def inside a function body
    for fn in ast.walk(tree):
        if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        local_fns: Set[str] = set()
        for sub in ast.walk(fn):
            if isinstance(sub, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                    and sub is not fn:
                local_fns.add(sub.name)
            elif isinstance(sub, ast.Assign) \
                    and isinstance(sub.value, ast.Lambda):
                local_fns.update(
                    t.id for t in sub.targets if isinstance(t, ast.Name))
        # one aliasing hop: `g = local_def; ... jax.jit(g)`
        for sub in ast.walk(fn):
            if isinstance(sub, ast.Assign) \
                    and isinstance(sub.value, ast.Name) \
                    and sub.value.id in local_fns:
                local_fns.update(
                    t.id for t in sub.targets if isinstance(t, ast.Name))
        for call in ast.walk(fn):
            if not (isinstance(call, ast.Call) and _is_jit_call(call.func)
                    and call.args):
                continue
            arg = call.args[0]
            if isinstance(arg, ast.Lambda) or (
                    isinstance(arg, ast.Name) and arg.id in local_fns):
                name = ("lambda" if isinstance(arg, ast.Lambda)
                        else arg.id)
                yield Finding(
                    path, call.lineno, "KJ006",
                    f"jax.jit over per-call-scope callable `{name}` in "
                    f"`{fn.name}` recompiles on every call; cache the "
                    "jitted fn (module level, instance memo, or an "
                    "explicit program cache)")


#: jnp calls that ALLOCATE a fresh (usually grown or copied) buffer —
#: a carry rebuilt through one of these reallocates every scan trip.
_CARRY_ALLOC_CALLS = {
    "concatenate", "stack", "vstack", "hstack", "dstack", "append",
    "pad", "tile", "repeat", "copy",
}
#: in-place carry-update spellings that let XLA donate the carry buffer
#: between trips.
_INPLACE_UPDATE_ATTRS = {
    "dynamic_update_slice", "dynamic_update_index_in_dim", "set", "add",
}


def _scope_walk(scope: ast.AST) -> Iterator[ast.AST]:
    """Walk a scope's own statements WITHOUT descending into nested
    function/lambda bodies (the nested defs themselves are yielded, so
    callers can collect them as this scope's local names)."""
    stack = (list(scope.body)
             if isinstance(scope, (ast.FunctionDef, ast.AsyncFunctionDef,
                                   ast.Module))
             else list(ast.iter_child_nodes(scope)))
    while stack:
        n = stack.pop()
        yield n
        if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef,
                          ast.Lambda)):
            continue
        stack.extend(ast.iter_child_nodes(n))


def _scan_bodies(tree: ast.AST) -> Iterator:
    """Yield ``(call_node, body_fn_node, carry_param_index)`` for every
    ``lax.scan(body, ...)`` / ``lax.fori_loop(lo, hi, body, init)`` call
    whose body resolves to a lambda or a ``def``/lambda bound in the
    call's own scope (nearest-scope resolution — two solver steps may
    both name their body ``body``)."""
    scopes = [tree] + [
        n for n in ast.walk(tree)
        if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))
    ]
    for scope in scopes:
        own = list(_scope_walk(scope))
        defs = {n.name: n for n in own if isinstance(n, ast.FunctionDef)}
        lambdas = {}
        for n in own:
            if isinstance(n, ast.Assign) and isinstance(n.value, ast.Lambda):
                for t in n.targets:
                    if isinstance(t, ast.Name):
                        lambdas[t.id] = n.value
        for call in own:
            if not (isinstance(call, ast.Call)
                    and isinstance(call.func, ast.Attribute)):
                continue
            root = _attr_root(call.func)
            attr = call.func.attr
            if attr == "scan" and root in {"lax", "jax"}:
                body_arg, carry_idx = (
                    call.args[0] if call.args else None), 0
            elif attr == "fori_loop" and root in {"lax", "jax"}:
                body_arg, carry_idx = (
                    call.args[2] if len(call.args) > 2 else None), 1
            else:
                continue
            if isinstance(body_arg, ast.Lambda):
                yield call, body_arg, carry_idx
            elif isinstance(body_arg, ast.Name):
                fn = defs.get(body_arg.id) or lambdas.get(body_arg.id)
                if fn is not None:
                    yield call, fn, carry_idx


def _check_scan_carry_realloc(tree: ast.AST, path: str) -> Iterator[Finding]:
    """KJ007: a scan/fori body whose carried value is rebuilt through an
    allocating jnp call (``jnp.concatenate(carry, ...)`` and friends)
    with no in-place update pattern anywhere in the body. XLA only
    reuses the carry buffer across trips when the body writes it in
    place; a grow/copy carry allocates a fresh O(carry) buffer per trip
    — O(model) state silently doubled inside the one program the
    megafused apply path is supposed to be."""
    for call, body, carry_idx in _scan_bodies(tree):
        # carry names: the carry parameter itself plus one unpacking hop
        # (`a, b = carry` — the solver idiom)
        args = body.args.args
        if len(args) <= carry_idx:
            continue
        carry_names = {args[carry_idx].arg}
        body_stmts = (body.body if isinstance(body.body, list)
                      else [ast.Expr(body.body)])
        for sub in ast.walk(ast.Module(body=body_stmts, type_ignores=[])):
            if isinstance(sub, ast.Assign) \
                    and isinstance(sub.value, ast.Name) \
                    and sub.value.id in carry_names:
                for t in sub.targets:
                    if isinstance(t, ast.Name):
                        carry_names.add(t.id)
                    elif isinstance(t, ast.Tuple):
                        carry_names.update(
                            e.id for e in t.elts if isinstance(e, ast.Name))

        has_inplace = False
        offender = None
        for sub in ast.walk(ast.Module(body=body_stmts, type_ignores=[])):
            if not isinstance(sub, ast.Call):
                continue
            func = sub.func
            if isinstance(func, ast.Attribute) \
                    and func.attr in _INPLACE_UPDATE_ATTRS:
                has_inplace = True
            elif isinstance(func, ast.Attribute) \
                    and func.attr in _CARRY_ALLOC_CALLS \
                    and _attr_root(func) in _JNP_NAMES:
                touched = {
                    n.id for n in ast.walk(sub)
                    if isinstance(n, ast.Name)
                    and isinstance(n.ctx, ast.Load)
                }
                if touched & carry_names and offender is None:
                    offender = (sub.lineno, func.attr)
        if offender is not None and not has_inplace:
            line, name = offender
            yield Finding(
                path, line, "KJ007",
                f"scan/fori_loop carry rebuilt via jnp.{name} every trip "
                "with no in-place update; use lax.dynamic_update_slice / "
                ".at[].set so XLA donates the carry buffer (scan-invariant "
                "model state belongs in the closure, not the carry)")


#: operator methods the concurrent scheduler may run simultaneously
#: across vertices — writes to shared state inside them are races.
#: Kept in lockstep with `analysis/effects.py`'s HOT_METHODS (the
#: graph-level KP511 pass over the same discipline).
_HOT_PATH_METHODS = {
    "apply", "apply_batch", "apply_batch_stream", "single_transform",
    "batch_transform", "batch_transform_stream", "batch_fn", "fuse",
    "_chunk_loop",
}
#: in-place container mutators.
_MUTATOR_CALLS = {
    "append", "extend", "insert", "add", "update", "setdefault",
    "pop", "popitem", "remove", "discard", "clear",
}
#: module-level names matching the sanctioned structure-keyed cache
#: idiom (program caches, pending-future registries, locks).
_SANCTIONED_GLOBAL_RE = re.compile(r"(CACHE|PENDING|LOCK|REGISTRY)", re.I)


def _chain_root(node: ast.AST) -> ast.AST:
    while isinstance(node, (ast.Attribute, ast.Subscript)):
        node = node.value
    return node


def _is_self_dict(node: ast.AST) -> bool:
    """``self.__dict__`` — the sanctioned instance-memo root."""
    return (isinstance(node, ast.Attribute) and node.attr == "__dict__"
            and isinstance(node.value, ast.Name)
            and node.value.id == "self")


def _is_self_dict_chain(node: ast.AST) -> bool:
    """``self.__dict__`` or ``self.__dict__[...]`` — a mutator call on
    either (``self.__dict__.setdefault``, ``self.__dict__['k'].append``)
    is the sanctioned memo idiom, not shared-state mutation."""
    if _is_self_dict(node):
        return True
    return isinstance(node, ast.Subscript) and _is_self_dict(node.value)


def _check_hot_path_state_write(tree: ast.AST, path: str) -> Iterator[Finding]:
    """KJ008: apply-time state writes under ``nodes/``/``workflow/`` —
    assignment to ``self.*`` or to a declared ``global``, and in-place
    mutation (subscript assignment or a mutator-method call) of a
    module-level container, inside an operator's hot-path methods
    (``apply``/``apply_batch``/``_chunk_loop``). The concurrent DAG
    scheduler (default on) may force two vertices simultaneously, so
    any such write is schedule-dependent — the KP511 race class,
    policed here at the file level with zero imports. Sanctioned:
    the ``self.__dict__[...]`` instance-memo idiom and module-level
    structure-keyed caches (``*CACHE*``/``*PENDING*``/``*LOCK*``)."""
    module_names = {
        t.id
        for stmt in (tree.body if isinstance(tree, ast.Module) else [])
        if isinstance(stmt, (ast.Assign, ast.AnnAssign))
        for t in (stmt.targets if isinstance(stmt, ast.Assign)
                  else [stmt.target])
        if isinstance(t, ast.Name)
    }

    def flagged_global(name: str) -> bool:
        return name in module_names and not _SANCTIONED_GLOBAL_RE.search(name)

    for cls in ast.walk(tree):
        if not isinstance(cls, ast.ClassDef):
            continue
        for fn in cls.body:
            if not isinstance(fn, ast.FunctionDef) \
                    or fn.name not in _HOT_PATH_METHODS:
                continue
            declared_globals: Set[str] = set()
            for sub in ast.walk(fn):
                if isinstance(sub, ast.Global):
                    declared_globals.update(sub.names)
            for sub in ast.walk(fn):
                if isinstance(sub, (ast.Assign, ast.AugAssign, ast.AnnAssign)):
                    targets = (sub.targets if isinstance(sub, ast.Assign)
                               else [sub.target])
                    for t in targets:
                        elts = t.elts if isinstance(t, ast.Tuple) else [t]
                        for e in elts:
                            root = _chain_root(e)
                            if isinstance(e, ast.Name) \
                                    and e.id in declared_globals:
                                yield Finding(
                                    path, sub.lineno, "KJ008",
                                    f"`{fn.name}` writes module global "
                                    f"`{e.id}`; two concurrently forced "
                                    "vertices would race on it")
                            elif isinstance(root, ast.Name) \
                                    and root.id == "self":
                                if isinstance(e, ast.Subscript) \
                                        and _is_self_dict(e.value):
                                    continue  # sanctioned memo idiom
                                yield Finding(
                                    path, sub.lineno, "KJ008",
                                    f"`{fn.name}` assigns instance state "
                                    f"`self.{_attr_name(e)}` at apply "
                                    "time; shared instances race under "
                                    "the concurrent scheduler (memoize "
                                    "via self.__dict__[...] instead)")
                            elif isinstance(e, (ast.Subscript, ast.Attribute)) \
                                    and isinstance(root, ast.Name) \
                                    and flagged_global(root.id):
                                yield Finding(
                                    path, sub.lineno, "KJ008",
                                    f"`{fn.name}` mutates module-level "
                                    f"container `{root.id}` at apply time")
                elif isinstance(sub, ast.Call) \
                        and isinstance(sub.func, ast.Attribute) \
                        and sub.func.attr in _MUTATOR_CALLS \
                        and not _is_self_dict_chain(sub.func.value):
                    root = _chain_root(sub.func.value)
                    if isinstance(root, ast.Name) and flagged_global(root.id):
                        yield Finding(
                            path, sub.lineno, "KJ008",
                            f"`{fn.name}` calls `{root.id}."
                            f"{sub.func.attr}(...)` on a module-level "
                            "container at apply time")
                    elif isinstance(root, ast.Name) and root.id == "self" \
                            and isinstance(sub.func.value,
                                           (ast.Attribute, ast.Subscript)):
                        # self.attr.append(...) mutates shared instance
                        # state exactly like self.attr[k] = v does; a
                        # direct self.add(...) METHOD call is not a
                        # container mutation (the receiver must be an
                        # attribute/subscript chain, as in effects.py)
                        yield Finding(
                            path, sub.lineno, "KJ008",
                            f"`{fn.name}` calls `self."
                            f"{_attr_name(sub.func.value)}."
                            f"{sub.func.attr}(...)` at apply time; "
                            "shared instances race under the concurrent "
                            "scheduler (memoize via self.__dict__[...] "
                            "instead)")


#: the library's two mesh axis names — the canonical constants live in
#: parallel/mesh.py (DATA_AXIS/MODEL_AXIS); everything else must import
#: them, so a mesh rename (or a 3-axis pod layout) is a one-line change.
_MESH_AXIS_LITERALS = {"data", "model"}
#: call names whose arguments are axis names / partition specs.
_SHARDING_CALL_NAMES = {
    "P", "PartitionSpec", "NamedSharding", "Mesh", "make_mesh",
}
#: collective ops taking a positional axis-name argument.
_COLLECTIVE_ATTRS = {
    "psum", "pmean", "pmax", "pmin", "all_gather", "all_to_all",
    "psum_scatter", "axis_index", "ppermute", "pshuffle",
}
#: kwarg names that carry mesh axis names.
_AXIS_KWARGS = {"axis", "axis_name", "axis_names"}


def _call_name(func: ast.AST) -> Optional[str]:
    if isinstance(func, ast.Name):
        return func.id
    if isinstance(func, ast.Attribute):
        return func.attr
    return None


def _axis_literals_in(node: ast.AST) -> Iterator[ast.Constant]:
    for sub in ast.walk(node):
        if isinstance(sub, ast.Constant) and isinstance(sub.value, str) \
                and sub.value in _MESH_AXIS_LITERALS:
            yield sub


def _check_axis_literals(tree: ast.AST, path: str) -> Iterator[Finding]:
    """KJ009 (axis-literal half, under ``nodes/``/``workflow/``): a bare
    ``"data"``/``"model"`` string in a sharding construction
    (`P`/`PartitionSpec`/`NamedSharding`/`Mesh`), a collective call's
    axis argument (`lax.psum(x, "data")`), an ``axis=``/``axis_name(s)=``
    kwarg, or a ``mesh.shape.get("data")`` lookup. Axis names are mesh
    *configuration*: hard-coding them in node/workflow code silently
    desynchronizes from `parallel.mesh.DATA_AXIS`/`MODEL_AXIS` the day
    the mesh layout changes. Plain string data (NLP word lists, dict
    keys) never matches — only these call contexts are inspected."""
    for call in ast.walk(tree):
        if not isinstance(call, ast.Call):
            continue
        name = _call_name(call.func)
        contexts: List[ast.AST] = []
        if name in _SHARDING_CALL_NAMES or name in _COLLECTIVE_ATTRS:
            contexts.extend(call.args)
        if name == "get" and isinstance(call.func, ast.Attribute) \
                and isinstance(call.func.value, ast.Attribute) \
                and call.func.value.attr == "shape":
            contexts.extend(call.args)
        for kw in call.keywords:
            if kw.arg in _AXIS_KWARGS:
                contexts.append(kw.value)
        seen_lines = set()
        for ctx in contexts:
            for lit in _axis_literals_in(ctx):
                if lit.lineno in seen_lines:
                    continue
                seen_lines.add(lit.lineno)
                yield Finding(
                    path, lit.lineno, "KJ009",
                    f"hard-coded mesh axis name {lit.value!r} in "
                    f"`{name}(...)`; import meshlib.DATA_AXIS/MODEL_AXIS "
                    "so the axis layout stays a one-place decision")


def _check_bare_device_put(tree: ast.AST, path: str) -> Iterator[Finding]:
    """KJ009 (device_put half, under ``parallel/``/``data/``): a
    ``jax.device_put(x)`` with no sharding/device argument in the layers
    that own placement. The default placement is device 0 — on a mesh
    that silently un-shards (and un-overlaps) whatever flows through;
    placement decisions in the parallel-adjacent hot paths must be
    explicit (`NamedSharding`, `leaf_sharding`, `mesh` helpers)."""
    for call in ast.walk(tree):
        if not isinstance(call, ast.Call):
            continue
        func = call.func
        is_dput = (
            isinstance(func, ast.Attribute) and func.attr == "device_put"
            and _attr_root(func) == "jax"
        ) or (isinstance(func, ast.Name) and func.id == "device_put")
        if not is_dput:
            continue
        if len(call.args) >= 2 or any(
                kw.arg in {"device", "sharding", "dst_sharding"} or
                kw.arg is None
                for kw in call.keywords):
            continue
        yield Finding(
            path, call.lineno, "KJ009",
            "jax.device_put without an explicit sharding defaults to "
            "device 0; parallel-layer placements must name their "
            "sharding (NamedSharding / data.dataset.leaf_sharding)")


def _check_output_layout_leak(tree: ast.AST, path: str) -> Iterator[Finding]:
    """KJ010 (under ``workflow/``/``nodes/``): a ``jax.jit``/``pjit``
    call with an ``in_shardings=`` keyword but no ``out_shardings=``.
    Half-constrained jits hand the output layout to XLA's partitioner:
    whatever placement compilation picks, the caller inherits — and the
    next stage boundary pays an implicit reshard to get back to the
    layout the plan expected. A call deliberate enough to pin its input
    layout must pin (or explicitly delegate) its output layout too."""
    for call in ast.walk(tree):
        if not isinstance(call, ast.Call):
            continue
        func = call.func
        name = None
        if isinstance(func, ast.Name):
            name = func.id
        elif isinstance(func, ast.Attribute):
            name = func.attr
        if name not in {"jit", "pjit"}:
            continue
        kwargs = {kw.arg for kw in call.keywords}
        if "in_shardings" in kwargs and "out_shardings" not in kwargs:
            yield Finding(
                path, call.lineno, "KJ010",
                f"`{name}(...)` passes in_shardings but no out_shardings; "
                "the output layout leaks to XLA's partitioner and "
                "downstream consumers re-shard implicitly — declare "
                "out_shardings")


def _is_f32_literal(node: ast.AST) -> bool:
    """`jnp.float32` / `np.float32` attribute, bare `float32`, or the
    string constant "float32"."""
    if isinstance(node, ast.Attribute) and node.attr == "float32" \
            and isinstance(node.value, ast.Name) \
            and node.value.id in (_NUMPY_NAMES | _JNP_NAMES):
        return True
    if isinstance(node, ast.Name) and node.id == "float32":
        return True
    return isinstance(node, ast.Constant) and node.value == "float32"


def _check_literal_precision_cast(tree: ast.AST, path: str
                                  ) -> Iterator[Finding]:
    """KJ011 (under ``workflow/``/``nodes/``): literal f32 casts inside
    ``fuse()`` / ``_chunk_loop`` bodies — the code that becomes part of
    a fused XLA program. Three forms: ``x.astype(jnp.float32)``,
    a direct ``jnp.float32(...)`` call (an f32 scalar param silently
    promotes a bf16 tensor), and ``asarray(..., jnp.float32)`` /
    ``dtype=jnp.float32`` call arguments. ``_build_program`` counts as
    a fused body too — its nested chunk_fn/per_shard closures are
    traced into the same XLA program the planner tags. Dtype literals
    OUTSIDE fused bodies (loaders, abstract_eval specs, host decode
    paths) are not this rule's business."""
    for fn in ast.walk(tree):
        if not isinstance(fn, ast.FunctionDef) \
                or fn.name not in {"fuse", "_chunk_loop", "_build_program"}:
            continue
        for sub in ast.walk(fn):
            if not isinstance(sub, ast.Call):
                continue
            func = sub.func
            if isinstance(func, ast.Attribute) and func.attr == "astype" \
                    and sub.args and _is_f32_literal(sub.args[0]):
                yield Finding(
                    path, sub.lineno, "KJ011",
                    "literal .astype(float32) in a fused-program body "
                    "defeats the precision policy; cast to the input's "
                    "dtype instead")
                continue
            if _is_f32_literal(func):
                yield Finding(
                    path, sub.lineno, "KJ011",
                    "literal float32(...) scalar in a fused-program "
                    "body: jnp promotion widens bf16 tensors against "
                    "f32 scalars — build the scalar from the input "
                    "dtype instead")
                continue
            literal_args = [a for a in sub.args if _is_f32_literal(a)]
            literal_kwargs = [kw for kw in sub.keywords
                              if kw.arg == "dtype"
                              and _is_f32_literal(kw.value)]
            if literal_args or literal_kwargs:
                name = _call_name(func) or "?"
                line = (literal_args[0].lineno if literal_args
                        else literal_kwargs[0].value.lineno)
                yield Finding(
                    path, line, "KJ011",
                    f"literal float32 dtype in `{name}(...)` inside a "
                    "fused-program body defeats the precision policy; "
                    "derive the dtype from the input instead")


#: attribute spellings that mean "transpose" on an array expression.
_TRANSPOSE_ATTRS = {"T", "mT"}
#: call names that permute axes (method or jnp.* form).
_TRANSPOSE_CALLS = {"transpose", "swapaxes", "moveaxis", "permute_dims"}


def _contains_transpose(node: ast.AST) -> Optional[int]:
    """Line number of a transpose buried in an expression — a ``.T`` /
    ``.mT`` attribute read, or a ``transpose``/``swapaxes``/
    ``moveaxis`` call — or None."""
    for sub in ast.walk(node):
        if isinstance(sub, ast.Attribute) and sub.attr in _TRANSPOSE_ATTRS \
                and isinstance(sub.ctx, ast.Load):
            return sub.lineno
        if isinstance(sub, ast.Call) and isinstance(sub.func, ast.Attribute) \
                and sub.func.attr in _TRANSPOSE_CALLS:
            return sub.lineno
    return None


def _check_transpose_reshape(tree: ast.AST, path: str) -> Iterator[Finding]:
    """KJ013 (under ``workflow/``/``nodes/``): a transpose-then-reshape
    chain inside a ``fuse()`` / ``_chunk_loop`` / ``_build_program``
    body — the code that becomes part of a fused XLA program. Two
    spellings are matched: ``<expr with transpose>.reshape(...)``
    (method chain, ``x.T.reshape(...)`` included) and
    ``jnp.reshape(<expr with transpose>, ...)``. A reshape over a
    permuted view forces the permuted buffer to materialize — a full
    write+read of the tensor invisible to the roofline's boundary
    bytes; the stage shows up as KP802 movement dominance at the graph
    level, and here at the file level with zero imports."""
    for fn in ast.walk(tree):
        if not isinstance(fn, ast.FunctionDef) \
                or fn.name not in {"fuse", "_chunk_loop", "_build_program"}:
            continue
        for sub in ast.walk(fn):
            if not isinstance(sub, ast.Call):
                continue
            func = sub.func
            if isinstance(func, ast.Attribute) and func.attr == "reshape":
                root = _attr_root(func)
                if root in _JNP_NAMES:
                    target = sub.args[0] if sub.args else None
                else:
                    target = func.value
                if target is not None and _contains_transpose(target):
                    yield Finding(
                        path, sub.lineno, "KJ013",
                        "transpose-then-reshape in a fused-program body: "
                        "the permuted buffer materializes before the "
                        "reshape (a full write+read the roofline's "
                        "boundary-bytes model cannot see); reorder the "
                        "computation or keep the axis order end-to-end")


#: the telemetry metric factories whose name argument KJ012 audits
#: (alias-tolerant: ``from ..telemetry import counter as _counter`` is
#: still the same registry entry point).
_METRIC_FACTORIES = {"counter", "gauge", "histogram"}


def _check_dynamic_metric_name(tree: ast.AST, path: str
                               ) -> Iterator[Finding]:
    """KJ012 (under ``workflow/``/``nodes/``): a
    ``counter/gauge/histogram`` call whose metric name is not a string
    literal. The registry is process-wide and created-on-first-use: a
    name formatted from a vertex id, label, or chunk index mints a new
    metric per distinct value — unbounded cardinality that grows the
    registry (and every trace's embedded metrics snapshot) for the
    life of the process. Both the module-level factories and
    registry/attribute forms (``telemetry.counter``,
    ``registry().gauge``) are matched; leading-underscore import
    aliases too. The attribute form is matched only on telemetry
    receivers (``telemetry.*`` / ``metrics.*`` modules, ``registry()``
    calls) so numeric APIs sharing a name — ``np.histogram``,
    ``jnp.histogram`` — never false-positive. A literal first argument
    (or ``name=`` literal) is the pass condition — constant-folding of
    f-strings is deliberately NOT attempted: an f-string with no
    placeholders is still a smell worth normalizing."""
    for call in ast.walk(tree):
        if not isinstance(call, ast.Call):
            continue
        func = call.func
        if isinstance(func, ast.Name):
            fname = func.id
        elif isinstance(func, ast.Attribute):
            fname = func.attr
            # the receiver must be the telemetry layer: a module whose
            # dotted name ends in telemetry/metrics, or a registry()
            # call — np.histogram / jnp.histogram are not metrics
            recv = func.value
            if isinstance(recv, ast.Call):
                rf = recv.func
                rname = (rf.id if isinstance(rf, ast.Name)
                         else rf.attr if isinstance(rf, ast.Attribute)
                         else "")
                if rname.lstrip("_") != "registry":
                    continue
            else:
                last = (recv.attr if isinstance(recv, ast.Attribute)
                        else recv.id if isinstance(recv, ast.Name)
                        else "")
                if last.lstrip("_") not in ("telemetry", "metrics"):
                    continue
        else:
            continue
        if fname.lstrip("_") not in _METRIC_FACTORIES:
            continue
        arg = call.args[0] if call.args else None
        if arg is None:
            for kw in call.keywords:
                if kw.arg == "name":
                    arg = kw.value
                    break
        if arg is None:
            continue
        if isinstance(arg, ast.Constant) and isinstance(arg.value, str):
            continue
        yield Finding(
            path, call.lineno, "KJ012",
            f"`{fname}(...)` with a dynamically formatted metric name "
            "in a hot path: per-value names mint unbounded registry "
            "cardinality — use one literal name and carry the "
            "dimension in a span arg")


def _kj018_emission_name(call: ast.Call):
    """The telemetry emission a call expresses — ``span``, a metric
    factory (``counter``/``gauge``/``histogram``), or a tracer
    ``counter_sample`` — or None. Attribute forms require a telemetry
    receiver (``telemetry.*`` / ``metrics.*`` / ``spans.*`` modules, a
    ``registry()``/``current_tracer()`` call, or a ``tracer`` object)
    so unrelated APIs sharing a name never false-positive."""
    func = call.func
    if isinstance(func, ast.Name):
        base = func.id.lstrip("_")
        if base == "span" or base in _METRIC_FACTORIES:
            return base
        return None
    if isinstance(func, ast.Attribute):
        base = func.attr.lstrip("_")
        if base != "span" and base != "counter_sample" \
                and base not in _METRIC_FACTORIES:
            return None
        recv = func.value
        if isinstance(recv, ast.Call):
            rf = recv.func
            rname = (rf.id if isinstance(rf, ast.Name)
                     else rf.attr if isinstance(rf, ast.Attribute)
                     else "")
            if rname.lstrip("_") in ("registry", "current_tracer"):
                return base
            return None
        last = (recv.attr if isinstance(recv, ast.Attribute)
                else recv.id if isinstance(recv, ast.Name)
                else "")
        if last.lstrip("_") in ("telemetry", "metrics", "spans", "tracer"):
            return base
    return None


def _check_trace_time_telemetry(tree: ast.AST, path: str
                                ) -> Iterator[Finding]:
    """KJ018 (under ``workflow/``/``nodes/``): a span or metric
    emission lexically inside a fused-program body. ``fuse()`` and
    ``_chunk_loop`` bodies are traced wholesale; ``_build_program`` is
    different — its top level is host build code (a build-time counter
    there is legitimate), but its nested ``chunk_fn``/``per_shard``
    closures ARE the traced program body, so only nested defs/lambdas
    are scanned there. An emission in traced code fires once per
    COMPILE, not once per run: the recorded latency is trace-time, the
    live percentile sketches ingest garbage, and warm re-runs emit
    nothing — the non-obvious twin of KJ002's numpy-under-jit."""
    for fn in ast.walk(tree):
        if not isinstance(fn, ast.FunctionDef):
            continue
        if fn.name in ("fuse", "_chunk_loop"):
            scopes = [fn]
        elif fn.name == "_build_program":
            scopes = [n for n in ast.walk(fn)
                      if isinstance(n, (ast.FunctionDef, ast.Lambda))
                      and n is not fn]
        else:
            continue
        for scope in scopes:
            for sub in ast.walk(scope):
                if not isinstance(sub, ast.Call):
                    continue
                name = _kj018_emission_name(sub)
                if name:
                    yield Finding(
                        path, sub.lineno, "KJ018",
                        f"`{name}(...)` inside a fused-program body "
                        "executes at trace time, not per run — the "
                        "emission records compile-time and corrupts "
                        "live percentiles; instrument at the dispatch "
                        "boundary instead")


def _attr_name(node: ast.AST) -> str:
    names = []
    while isinstance(node, (ast.Attribute, ast.Subscript)):
        if isinstance(node, ast.Attribute):
            names.append(node.attr)
        node = node.value
    return names[-1] if names else "?"


_BOUNDED_QUEUE_CLASSES = {"Queue", "LifoQueue", "PriorityQueue"}
#: receiver names that mark a list as a request buffer (KJ019): the
#: serving vocabulary for "work waiting to be dispatched".
_REQUEST_BUFFER_RE = re.compile(
    r"(queue|pending|request|backlog|inbox|buffer)s?$", re.IGNORECASE)


def _kj019_queue_call(call: ast.Call) -> Optional[str]:
    """The queue class name when ``call`` constructs a stdlib queue
    (``queue.Queue(...)`` or a bare imported ``Queue(...)``), else
    None. Receiver-filtered like KJ012: ``multiprocessing.Queue`` et
    al. resolve through the same names, which is fine — the bounding
    discipline is identical."""
    func = call.func
    if isinstance(func, ast.Name):
        name = func.id
    elif isinstance(func, ast.Attribute) and isinstance(func.value,
                                                        ast.Name):
        name = func.attr
    else:
        return None
    if name in _BOUNDED_QUEUE_CLASSES or name == "SimpleQueue":
        return name
    return None


def _kj019_unbounded(call: ast.Call) -> bool:
    """Is this bounded-capable queue construction provably unbounded?
    No maxsize argument at all, or a literal maxsize ≤ 0 (the stdlib's
    'infinite' spelling). A non-literal maxsize expression is accepted
    — the capacity is a decision, which is all the rule demands."""
    args = list(call.args)
    maxsize: Optional[ast.AST] = args[0] if args else None
    for kw in call.keywords:
        if kw.arg == "maxsize":
            maxsize = kw.value
        elif kw.arg is None:
            return False  # **kwargs splat: cannot prove
    if maxsize is None:
        return True
    if isinstance(maxsize, ast.Constant) and isinstance(
            maxsize.value, (int, float)):
        return maxsize.value <= 0
    if isinstance(maxsize, ast.UnaryOp) and isinstance(maxsize.op,
                                                       ast.USub):
        return True  # a negative literal, however spelled
    return False


def _check_unbounded_request_buffer(tree: ast.AST, path: str,
                                    serving: bool) -> Iterator[Finding]:
    """KJ019: unbounded ``queue.Queue()`` constructions (serving/ and
    workflow/), plus — under serving/ only — ``SimpleQueue()`` and bare
    list-appends onto request-buffer-named receivers. The load-shed
    discipline: a serving queue must be able to say no."""
    for node in ast.walk(tree):
        if isinstance(node, ast.Call):
            cls = _kj019_queue_call(node)
            if cls == "SimpleQueue":
                if serving:
                    yield Finding(
                        path, node.lineno, "KJ019",
                        "`SimpleQueue()` is unbounded by construction "
                        "— a serving queue must be bounded so a full "
                        "queue sheds (use queue.Queue(maxsize=execution"
                        "_config().serving_queue_depth))")
                continue
            if cls is not None and _kj019_unbounded(node):
                yield Finding(
                    path, node.lineno, "KJ019",
                    f"`{cls}()` without a positive maxsize is an "
                    "unbounded request buffer — overload becomes "
                    "unbounded memory and queueing delay instead of a "
                    "shed; size it (serving_queue_depth is the "
                    "sanctioned knob)")
            continue
        if not serving:
            continue
        if (isinstance(node, ast.Expr)
                and isinstance(node.value, ast.Call)
                and isinstance(node.value.func, ast.Attribute)
                and node.value.func.attr == "append"):
            recv = node.value.func.value
            recv_name = (recv.attr if isinstance(recv, ast.Attribute)
                         else recv.id if isinstance(recv, ast.Name)
                         else None)
            if recv_name and _REQUEST_BUFFER_RE.search(
                    recv_name.lstrip("_")):
                yield Finding(
                    path, node.lineno, "KJ019",
                    f"bare list-append onto `{recv_name}` grows a "
                    "request buffer without bound — route requests "
                    "through a bounded queue.Queue so overload sheds "
                    "instead of accumulating")


def _check_missing_donate(tree: ast.AST, path: str) -> Iterator[Finding]:
    for fn in ast.walk(tree):
        if not isinstance(fn, ast.FunctionDef):
            continue
        if not _STEP_NAME_RE.search(fn.name):
            continue
        dec = _jit_decorator(fn)
        if dec is None:
            continue
        if "donate_argnums" not in _decorator_kwargs(dec):
            yield Finding(
                path, fn.lineno, "KJ003",
                f"jitted solver step `{fn.name}` has no donate_argnums; "
                "its state buffers reallocate every iteration")


#: call receivers whose attribute calls block on the network.
_NETWORK_RECEIVERS = {"urllib", "requests", "socket", "http", "httplib"}
#: attribute names that read/block regardless of receiver spelling
#: (urllib.request.urlopen, socket.create_connection).
_BLOCKING_ATTRS = {"urlopen", "create_connection", "getaddrinfo"}
#: Path read methods — Path(...).read_text() in a hot method is file
#: I/O just like open().read().
_PATH_READ_ATTRS = {"read_text", "read_bytes"}


def _check_blocking_host_io(tree: ast.AST, path: str) -> Iterator[Finding]:
    """KJ014 (under ``workflow/``/``nodes/``): blocking host I/O inside
    an operator hot-path method — ``time.sleep``, ``open(...)`` /
    ``Path.read_*`` file reads, or urllib/requests/socket network
    calls. The non-device companion of KJ005's blocking-host-pull rule:
    a sleep or synchronous read on the apply path stalls every request
    for the full host-call latency, invisibly to the roofline time
    model that prices the KP903 serving bound."""
    for cls in ast.walk(tree):
        if not isinstance(cls, ast.ClassDef):
            continue
        for fn in cls.body:
            if not isinstance(fn, ast.FunctionDef) \
                    or fn.name not in _HOT_PATH_METHODS:
                continue
            for sub in ast.walk(fn):
                if not isinstance(sub, ast.Call):
                    continue
                func = sub.func
                offense = None
                if isinstance(func, ast.Name):
                    if func.id == "open":
                        offense = "`open(...)` file I/O"
                    elif func.id in ("urlopen", "sleep"):
                        offense = f"`{func.id}(...)`"
                elif isinstance(func, ast.Attribute):
                    root = _chain_root(func)
                    root_id = root.id if isinstance(root, ast.Name) else ""
                    if func.attr == "sleep" and root_id == "time":
                        offense = "`time.sleep(...)`"
                    elif func.attr in _BLOCKING_ATTRS:
                        offense = f"`{root_id or '...'}.{func.attr}(...)`"
                    elif root_id in _NETWORK_RECEIVERS:
                        offense = f"`{root_id}.{func.attr}(...)` network call"
                    elif func.attr in _PATH_READ_ATTRS:
                        offense = f"`.{func.attr}()` file read"
                    elif func.attr == "read" and isinstance(
                            func.value, ast.Call) and isinstance(
                            func.value.func, ast.Name) \
                            and func.value.func.id == "open":
                        offense = "`open(...).read()`"
                if offense is not None:
                    yield Finding(
                        path, sub.lineno, "KJ014",
                        f"{offense} in hot-path method `{fn.name}`: "
                        "blocking host I/O stalls every request for the "
                        "full call latency and is invisible to the "
                        "KP903 serving latency bound — hoist it to "
                        "construction/fit time or the serving ingress")


def _check_manual_chunk_knob(tree: ast.AST, path: str) -> Iterator[Finding]:
    """KJ015 (under ``workflow/``/``nodes/``, the config definition
    site ``workflow/env.py`` excluded by the dispatcher): a direct
    ``<config>.chunk_size`` attribute read, or any expression carrying
    the ``"KEYSTONE_CHUNK_SIZE"`` env-key literal. Since PR 15 the
    chunk size is an optimizer decision — the planner's chosen chunk
    reaches the host batcher and the KP2xx/KP8xx static models through
    ONE resolution (`workflow.env.resolved_chunk_size`); a module
    reading the raw knob executes (or models) a chunking the planner
    did not decide."""
    def config_receiver(node) -> bool:
        # cfg.chunk_size / config.chunk_size / execution_config().chunk_size
        if isinstance(node, ast.Name):
            return node.id in ("cfg", "config", "exec_config",
                               "execution_config")
        if isinstance(node, ast.Call):
            fn = node.func
            name = fn.id if isinstance(fn, ast.Name) else (
                fn.attr if isinstance(fn, ast.Attribute) else "")
            return name == "execution_config"
        return False

    for sub in ast.walk(tree):
        if isinstance(sub, ast.Attribute) and sub.attr == "chunk_size" \
                and isinstance(sub.ctx, ast.Load) \
                and config_receiver(sub.value):
            yield Finding(
                path, sub.lineno, "KJ015",
                "direct `.chunk_size` config read bypasses the unified "
                "planner's chunk decision — call "
                "workflow.env.resolved_chunk_size() (or take an "
                "explicit parameter) instead")
        elif isinstance(sub, ast.Constant) \
                and sub.value == "KEYSTONE_CHUNK_SIZE":
            yield Finding(
                path, sub.lineno, "KJ015",
                "direct KEYSTONE_CHUNK_SIZE env read bypasses the "
                "unified planner's chunk decision — the env knob is "
                "resolved once by ExecutionConfig; read "
                "workflow.env.resolved_chunk_size() instead")


def _check_pallas_outside_ops(tree: ast.AST, path: str) -> Iterator[Finding]:
    """KJ016 (everywhere except ``ops/``): a ``pl.pallas_call`` /
    ``pallas.pallas_call`` / bare ``pallas_call`` invocation outside
    the one audited kernel home. Comments and docstrings naming the
    API do not trip this — only a real call expression does."""
    for sub in ast.walk(tree):
        if not isinstance(sub, ast.Call):
            continue
        fn = sub.func
        name = fn.attr if isinstance(fn, ast.Attribute) else (
            fn.id if isinstance(fn, ast.Name) else "")
        if name == "pallas_call":
            yield Finding(
                path, sub.lineno, "KJ016",
                "pallas_call outside keystone_tpu/ops/ — kernels live "
                "in ops/ (with a pure-jnp *_reference oracle) so the "
                "lint.sh chain-kernel audit, the live-chip canary, and "
                "the KEYSTONE_CHAIN_KERNELS kill switch cover them; "
                "move the kernel there and call the builder")


def _check_hardcoded_kernel_geometry(tree: ast.AST,
                                     path: str) -> Iterator[Finding]:
    """KJ017 (``ops/`` only): a hard-coded VMEM byte budget (a
    ``<< 20`` MiB shift or a >=1 MiB integer constant) outside the one
    sanctioned ``_VMEM_BUDGET`` definition, or a literal leading
    block-row count in a ``pl.BlockSpec`` shape tuple. A leading
    literal of 1 is a broadcast/scalar block dimension, not a chosen
    batch block — only literals > 1 trip."""
    sanctioned: Set[int] = set()
    for sub in ast.walk(tree):
        if isinstance(sub, ast.Assign) and any(
                isinstance(t, ast.Name) and t.id == "_VMEM_BUDGET"
                for t in sub.targets):
            sanctioned.update(id(inner) for inner in ast.walk(sub))
    mib = 1 << 20
    for sub in ast.walk(tree):
        if id(sub) in sanctioned:
            continue
        if (isinstance(sub, ast.BinOp) and isinstance(sub.op, ast.LShift)
                and isinstance(sub.right, ast.Constant)
                and isinstance(sub.right.value, int)
                and sub.right.value >= 20):
            yield Finding(
                path, sub.lineno, "KJ017",
                "hard-coded VMEM byte budget (MiB shift) outside "
                "chain_kernels._VMEM_BUDGET — route the geometry "
                "through the shared chooser "
                "(chain_vmem_bytes/chain_block_rows) so the KP1003 "
                "static proof covers it")
        elif (isinstance(sub, ast.Constant) and isinstance(sub.value, int)
                and not isinstance(sub.value, bool) and sub.value >= mib):
            yield Finding(
                path, sub.lineno, "KJ017",
                "hard-coded >=1 MiB byte constant outside "
                "chain_kernels._VMEM_BUDGET — a second inline VMEM "
                "arithmetic the KP1003 static proof cannot see")
        elif isinstance(sub, ast.Call):
            fn = sub.func
            name = fn.attr if isinstance(fn, ast.Attribute) else (
                fn.id if isinstance(fn, ast.Name) else "")
            if name == "BlockSpec" and sub.args:
                shape = sub.args[0]
                if (isinstance(shape, (ast.Tuple, ast.List)) and shape.elts
                        and isinstance(shape.elts[0], ast.Constant)
                        and isinstance(shape.elts[0].value, int)
                        and not isinstance(shape.elts[0].value, bool)
                        and shape.elts[0].value > 1):
                    yield Finding(
                        path, shape.elts[0].lineno, "KJ017",
                        "literal leading block-row count in a "
                        "pl.BlockSpec shape — the batch block is the "
                        "shared chooser's decision "
                        "(chain_block_rows), not a constant; a pinned "
                        "block dodges the KP1003 VMEM proof")


# ----------------------------------------------------------------- driver


#: constructors/loaders whose result is an out-of-core (host-tier)
#: dataset — the names KJ020 tracks assignments from
_OOC_CONSTRUCTORS = {"OutOfCoreDataset", "SpilledDataset",
                     "out_of_core_from_shards", "out_of_core_npy_loader",
                     "synthetic_out_of_core"}

#: numpy-level whole-array drains (np.<attr> / numpy.<attr>)
_OOC_NP_DRAINS = {"asarray", "array", "stack", "concatenate"}


def _check_ooc_whole_drain(tree: ast.AST, path: str) -> Iterator[Finding]:
    """KJ020 (under ``data/``/``workflow/``): whole-dataset
    materialization of an out-of-core source. Names bound from the
    out-of-core constructors/loaders are tracked per module; feeding a
    tracked name to a numpy whole-array drain or ``list()``/``tuple()``
    defeats the bounded-residency contract the windowed prefetcher
    provides. The classes' own ``materialize()``/``rehydrate()``/
    ``numpy()`` methods are not flagged — they ARE the sanctioned,
    greppable full-drain decision points."""
    tracked: Set[str] = set()
    for node in ast.walk(tree):
        if not isinstance(node, ast.Assign) \
                or not isinstance(node.value, ast.Call):
            continue
        func = node.value.func
        name = (func.id if isinstance(func, ast.Name)
                else func.attr if isinstance(func, ast.Attribute)
                else None)
        if name in _OOC_CONSTRUCTORS:
            for tgt in node.targets:
                if isinstance(tgt, ast.Name):
                    tracked.add(tgt.id)
    if not tracked:
        return
    for call in ast.walk(tree):
        if not isinstance(call, ast.Call):
            continue
        func = call.func
        drain = None
        if isinstance(func, ast.Attribute) \
                and func.attr in _OOC_NP_DRAINS \
                and _attr_root(func) in {"np", "numpy"}:
            drain = f"np.{func.attr}"
        elif isinstance(func, ast.Name) and func.id in {"list", "tuple"}:
            drain = func.id
        if drain is None:
            continue
        hit = next((a.id for a in call.args
                    if isinstance(a, ast.Name) and a.id in tracked), None)
        if hit is None:
            continue
        yield Finding(
            path, call.lineno, "KJ020",
            f"{drain}({hit}) drains an out-of-core dataset whole — "
            "stream it (window_iter()/map_windowed()) or make the full "
            f"drain explicit ({hit}.materialize()/.numpy())")


def lint_file(path: Path, repo_root: Optional[Path] = None) -> List[Finding]:
    src = path.read_text()
    try:
        tree = ast.parse(src, filename=str(path))
    except SyntaxError as e:
        return [Finding(str(path), e.lineno or 0, "KJ000",
                        f"syntax error: {e.msg}")]
    rel = str(path if repo_root is None else path.relative_to(repo_root))
    findings: List[Finding] = []
    findings.extend(_check_numpy_in_jit(tree, rel))
    findings.extend(_check_wall_clock_duration(tree, rel))
    posix = rel.replace("\\", "/") + "/"
    if "nodes/" in posix:
        findings.extend(_check_loop_accumulation(tree, rel))
    if "nodes/learning" in posix:
        findings.extend(_check_missing_donate(tree, rel))
    if "workflow/" in posix or "nodes/" in posix:
        findings.extend(_check_blocking_host_pull(tree, rel))
        findings.extend(_check_fresh_jit(tree, rel))
        findings.extend(_check_scan_carry_realloc(tree, rel))
        findings.extend(_check_hot_path_state_write(tree, rel))
        findings.extend(_check_axis_literals(tree, rel))
        findings.extend(_check_output_layout_leak(tree, rel))
        findings.extend(_check_literal_precision_cast(tree, rel))
        findings.extend(_check_dynamic_metric_name(tree, rel))
        findings.extend(_check_trace_time_telemetry(tree, rel))
        findings.extend(_check_transpose_reshape(tree, rel))
        findings.extend(_check_blocking_host_io(tree, rel))
        if not posix.endswith("workflow/env.py/"):
            # env.py IS the knob's definition + resolution site
            findings.extend(_check_manual_chunk_knob(tree, rel))
    if "serving/" in posix or "workflow/" in posix:
        findings.extend(_check_unbounded_request_buffer(
            tree, rel, serving="serving/" in posix))
    if "parallel/" in posix or "data/" in posix:
        findings.extend(_check_bare_device_put(tree, rel))
    if "data/" in posix or "workflow/" in posix:
        findings.extend(_check_ooc_whole_drain(tree, rel))
    if "ops/" not in posix:
        findings.extend(_check_pallas_outside_ops(tree, rel))
    else:
        findings.extend(_check_hardcoded_kernel_geometry(tree, rel))

    # nested loops make ast.walk revisit inner statements: keep one
    # finding per (line, rule)
    findings = list(dict.fromkeys(findings))

    # per-line suppression: # keystone: ignore[KJ001,KJ002]
    lines = src.splitlines()
    kept = []
    for f in findings:
        line = lines[f.line - 1] if 0 < f.line <= len(lines) else ""
        m = _IGNORE_RE.search(line)
        if m and f.rule in {r.strip() for r in m.group(1).split(",")}:
            continue
        kept.append(f)
    return kept


def iter_py_files(paths: List[str]) -> Iterator[Path]:
    for p in paths:
        path = Path(p)
        if path.is_dir():
            yield from sorted(path.rglob("*.py"))
        elif path.suffix == ".py":
            yield path


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("paths", nargs="*", default=["keystone_tpu"])
    ap.add_argument("--list-rules", action="store_true")
    ap.add_argument("--json", action="store_true",
                    help="machine-readable findings (CI annotation)")
    args = ap.parse_args(argv)
    if args.list_rules:
        for rule, desc in sorted(RULES.items()):
            print(f"{rule}  {desc}")
        return 0
    repo_root = Path(__file__).resolve().parent.parent
    findings: List[Finding] = []
    for f in iter_py_files(args.paths or ["keystone_tpu"]):
        root = repo_root if f.resolve().is_relative_to(repo_root) else None
        findings.extend(lint_file(f.resolve() if root else f, repo_root=root))
    if args.json:
        import json

        print(json.dumps({
            "findings": [f._asdict() for f in findings],
            "total": len(findings),
        }, indent=2))
        return 1 if findings else 0
    for finding in findings:
        print(finding)
    if findings:
        print(f"jaxlint: {len(findings)} finding(s)", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
