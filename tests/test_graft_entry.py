"""`__graft_entry__.py`, the driver's two entry points, rehearsed on the
CPU: the forward step of `entry()` jits and runs, and
`dryrun_multichip(4)` passes on four of the eight virtual devices."""

import os
import sys

import jax
import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:  # the module lives at the root of the checkout
    sys.path.insert(0, REPO)

import __graft_entry__ as graft  # noqa: E402


def test_entry_returns_a_step_that_jits_and_runs():
    fn, args = graft.entry()
    (images,) = args
    classes = np.asarray(jax.jit(fn)(*args))
    assert classes.shape == (images.shape[0],)
    assert classes.dtype.kind == "i"
    assert ((0 <= classes) & (classes < 10)).all()
    np.testing.assert_array_equal(classes, np.asarray(fn(*args)))


def test_dryrun_multichip_passes_on_four_virtual_devices(capsys):
    """The pipeline fit, the solver matrix, the component matrix and the
    planner's record on a (2, 2) ``data`` x ``model`` mesh, each against
    its one-device fit; a failure is an `AssertionError` from inside."""
    graft.dryrun_multichip(4)
    out = capsys.readouterr().out
    assert "dryrun_multichip(4): mesh {'data': 2, 'model': 2}" in out
    assert "1-device agreement" in out
    assert "solver matrix: 9 cells" in out
    assert "component matrix: 5 families ok" in out
    assert "planner kill-switch parity: predictions identical ok" in out
