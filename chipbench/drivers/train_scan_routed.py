"""Driver ``train_scan_routed``: ``train_scan``, with a check that shows
the reference what the program's routers read.

A sparse-expert layer's choice of experts is discontinuous.  The program's
products run at one bfloat16 pass, the reference's at 'highest', so a
router's INPUT differs between them by rounding, and a token whose last
chosen and first passed-over scores lie within that takes another expert on
one side: a whole row appears or vanishes, and where several expert layers
follow one another every gradient moves by ten percent and more, which
says nothing about either side (``configs/lfm2_8b_a1b.py CHECKS``).  So the
check's step also fetches the variables the configuration names
(``build``'s ``check_fetches``: each router's input), and ``reference``
is shown them (``observed``): it makes the discrete choice from what the
program's router read, with its own router and weights, keeps everything
continuous its own, and says how far its own router input lies from the
one it was shown.  That distance is held too (``router_input_rel_tol`` of
the ``CHECKS`` entry): the program is never its own witness.

Everything else is ``train_scan``'s, whose ``run`` this calls.  ``--set
control=<json>`` hands ``reference`` a control (a lower precision, a
fault) that the check has to fail; no measured run carries it.
"""
from __future__ import annotations

from unittest import mock

import numpy as np

from chipbench.drivers import train_scan
from chipbench.lib import check, weights


def _check_against_reference(ctx, exe, built, start, batch_sharding, feeds,
                             first_loss):
    main, sizes = built["main"], ctx.sizes
    names = list(sizes["check_params"])
    shown = built["check_fetches"]              # {key: variable of main}
    results = {}
    for spec in ctx.config.CHECKS:
        start.restore()
        params = start.state()
        sample = weights.make_feeds(built["feeds"], ctx.cell["check_batch"],
                                    ctx.seed_for("sample"), batch_sharding)
        got = exe.run(main, feed=sample, is_test=spec["is_test"],
                      fetch_list=[built["loss"]]
                      + [f"{n}@GRAD" for n in names] + list(shown.values()))
        ctx.mark(f"stepped_{spec['name']}")
        grads = dict(zip(names, got[1:1 + len(names)]))
        observed = dict(zip(shown, got[1 + len(names):]))
        ref_loss, ref_grads, saw = ctx.config.reference(
            "train", params, {k: np.asarray(v) for k, v in sample.items()},
            sizes, frozen_stats=spec["is_test"], observed=observed,
            control=ctx.cell.get("control"))
        held = check.compare_training(spec, got[0], grads, ref_loss,
                                      ref_grads)
        apart = max(saw[key]["input_rel_err"] for key in shown)
        held["ok"] = bool(held["ok"]
                          and apart <= spec["router_input_rel_tol"])
        results[spec["name"]] = {**held, "router_input_rel_err": apart,
                                 "routers": saw}
    return results


def run(ctx) -> dict:
    if ctx.cell.get("check_window_loss"):
        raise ValueError("train_scan_routed holds no window loss")
    with mock.patch.object(train_scan, "_check_against_reference",
                           _check_against_reference):
        return train_scan.run(ctx)
