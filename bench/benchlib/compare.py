"""The comparison that decides ``correct``.

The simulator promises exact statistics: a run on the chip must give
the same virtual time, epoch, tick and delivery counts and the same
modelled statistics as the plain reference given the same inputs.  So
the one number compared is the count of statistics that differ, and its
limit is 0.
"""
from __future__ import annotations

LIMITS = {"stat_mismatches": 0}
# the precision below each that a configuration may state for its time
LOWER = {"float32": "bfloat16"}


def control(simulate, config, inputs, point, until, ref: dict) -> dict:
    """The control: the plain reference put in the program's place with
    its virtual time in the precision below the one the configuration
    states (bfloat16 for float32).  Its epochs are capped a little past
    the reference's, since a clock that cannot advance never drains."""
    from benchlib.refengine import Num
    return simulate(config, inputs, point, until,
                    num=Num(LOWER[config["time_dtype"]]),
                    max_epochs=2 * ref["epochs"] + 1000)


def mismatches(got: dict, ref: dict) -> list[str]:
    """Names of the statistics of ``ref`` that ``got`` does not equal
    (an element of a per-instance list counts on its own)."""
    bad = []
    for key, want in ref.items():
        have = got.get(key)
        if isinstance(want, list):
            have = have if isinstance(have, list) else []
            for i, w in enumerate(want):
                if i >= len(have) or have[i] != w:
                    bad.append(f"{key}[{i}]")
            bad += [f"{key}[{i}]" for i in range(len(want), len(have))]
        elif have != want:
            bad.append(key)
    return bad


def judge(pairs: list[tuple[str, dict, dict]]) -> dict:
    """Compare each ``(label, program stats, reference stats)`` pair.

    Returns the numbers compared (``numbers``: name -> (value, limit)),
    ``correct``, and up to a few mismatching jobs for the log."""
    count, shown = 0, []
    for label, got, ref in pairs:
        bad = mismatches(got, ref)
        count += len(bad)
        if bad and len(shown) < 4:
            shown.append(f"{label}: " + ", ".join(
                f"{k} program {_at(got, k)} reference {_at(ref, k)}"
                for k in bad[:6]))
    numbers = {"stat_mismatches": (count, LIMITS["stat_mismatches"])}
    return {"numbers": numbers, "shown": shown,
            "correct": bool(pairs) and all(v <= lim for v, lim
                                           in numbers.values())}


def _at(stats: dict, key: str):
    if key.endswith("]"):
        name, ix = key[:-1].split("[")
        vals = stats.get(name)
        i = int(ix)
        return vals[i] if isinstance(vals, list) and i < len(vals) else None
    return stats.get(key)
