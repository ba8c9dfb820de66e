"""What the benchmark asks of a configuration that is cut to fit the chip.

A configuration keeps its source's widths; what may be cut is how much of
the model one chip holds (depth by whole layer periods, or one chip's share
of a stated deployment).  Each cut is written down twice and the two are
held to each other: ``BENCHMARK.json``'s entry names the cut keys (bare
names, as the driver's contract asks of ``reduced``), and the
configuration's own file, whose values ``build`` reads, says for each
``<key>: <published> -> <run>`` and what ``deployment`` the cut stands for.
That no WIDTH is cut is the reviewer's (and the driver's) to read from the
keys: a machine cannot tell a width from a count.
"""
from __future__ import annotations

import json

MAX_CUTS = 8
MAX_CUT_CHARS = 100
MAX_DEPLOYMENT_CHARS = 300


def _json(value) -> str:
    """Canonical text, so that values compare as JSON: 1 is not 1.0 or true."""
    return json.dumps(value, sort_keys=True)


def _canonical(text: str) -> str:
    return _json(json.loads(text))


def reduced_problems(entry, data) -> list[str]:
    """Why the configuration ``entry`` (of ``BENCHMARK.json``'s ``configs``)
    with the file ``data`` (its ``configs/<name>.json``) is not admitted;
    empty when it is.  Each problem names the key or the string at fault."""
    cuts = data.get("reduced")
    if not isinstance(cuts, list) or len(cuts) > MAX_CUTS or not all(
            isinstance(c, str) and len(c) <= MAX_CUT_CHARS for c in cuts):
        return [f"the file's 'reduced' is not a list of at most {MAX_CUTS} "
                f"strings of at most {MAX_CUT_CHARS} characters each: "
                f"{cuts!r}"]
    problems, keys = [], []
    for cut in cuts:
        key, colon, values = cut.partition(": ")
        published, arrow, run = values.partition(" -> ")
        keys.append(key)
        if not (colon and arrow):
            problems.append(
                f"{cut!r} does not read '<key>: <published> -> <run>'")
            continue
        try:
            published, run = _canonical(published), _canonical(run)
        except ValueError:
            problems.append(f"{cut!r}: <published> and <run> are not JSON")
            continue
        if key not in data:
            problems.append(f"{cut!r}: the file has no top-level key {key!r}")
        elif _json(data[key]) != run:
            problems.append(f"{cut!r}: the file runs {key} = "
                            f"{_json(data[key])}, not {run}")
        if published == run:
            problems.append(f"{cut!r}: {key} is not changed from the source")
        if keys.count(key) > 1:
            problems.append(f"{cut!r}: {key} is named by more than one string")
    if entry.get("reduced") != keys:
        problems.append(
            f"the entry's 'reduced' {entry.get('reduced')!r} is not the "
            f"list of keys the file's 'reduced' names, {keys!r}")
    deployment = data.get("deployment")
    if cuts and not (isinstance(deployment, str) and
                     0 < len(deployment.strip()) <= MAX_DEPLOYMENT_CHARS):
        problems.append(
            "a cut configuration's file states its 'deployment' (how many "
            "chips, how the model is divided, what this chip holds) in 1 to "
            f"{MAX_DEPLOYMENT_CHARS} characters: {deployment!r}")
    return problems
