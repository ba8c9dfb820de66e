"""Driver ``train_scan_fresh_start``: ``train_scan`` for a program whose
state is more than half the chip.

``train_scan`` returns to the seeded start by running the startup program
over the state the window left, and the startup program's results stand
beside what they replace until the scope takes them: twice the state.  A
step that holds 9.3 GB of weights and optimizer moments on a 16.9 GB chip
cannot be restarted that way ("Attempting to allocate 128.00M ... 67.60M
free").  Here the return to the start first RELEASES the program's
persistable state (``Scope.delete``, what a user does before initialising a
model again), then runs the same startup program and the same seeded draw:
the start is the same values, and the startup step is the one the first
start compiled (no state in the scope, so the same fingerprint).

``--set control=<json>`` hands the configuration's ``reference`` a control
(a lower precision, a fault) that the check has to FAIL, through the same
comparison as a measured run's; no measured run carries it.  Everything
else is ``train_scan``'s, whose ``run`` this calls.

TEMPORARY (PERF.md section 7): both hand-overs replace a name inside
``train_scan`` while it runs, which is no extension point, so ``run`` counts
them and RAISES if ``train_scan`` came to its end without having taken them
(an edit there that stops looking the names up must fail loudly, not measure
the wrong thing).  With ``_Start.restore`` releasing the state itself and
``train_scan`` passing ``ctx.cell.get("control")`` this file goes.
"""
from __future__ import annotations

from unittest import mock

from chipbench.drivers import train_scan


class _Start(train_scan._Start):
    restores = 0                 # how often ``train_scan`` came through here

    def restore(self):
        import paddle_tpu as pt

        scope, block = pt.global_scope(), self.built["main"].global_block()
        for name in [n for n in scope.keys()
                     if block.has_var(n) and block.var(n).persistable]:
            scope.delete(name)
        super().restore()
        _Start.restores += 1


def run(ctx) -> dict:
    control = ctx.cell.get("control")
    plain, handed = ctx.config.reference, []

    def reference(*args, **kwargs):
        handed.append(control)
        return plain(*args, **kwargs, control=control)

    restores = _Start.restores
    with mock.patch.object(train_scan, "_Start", _Start), \
            mock.patch.object(ctx.config, "reference", reference):
        verdict = train_scan.run(ctx)
    if _Start.restores - restores < 2 or not handed:
        raise RuntimeError(
            "train_scan_fresh_start: train_scan ran to its end without "
            f"this driver's start ({_Start.restores - restores} restores; "
            f"the first and the check's are two) or reference ({len(handed)} "
            "calls): it no longer looks up the names replaced here")
    return verdict
