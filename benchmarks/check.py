"""The arithmetic that decides ``correct``: each number compared sits
beside a limit of its own (``benchmarks/limits/<cell>.json``); a run is
correct when every number is at or under its limit."""

import math
import statistics


def worst_leaf_gap(prog, ref, skip=()):
    """Widest gap between the program's and the reference's norm of one
    leaf — the gap between the norms, not the norm of the difference —
    against the reference's norm of that leaf or of the median leaf,
    whichever is larger (some leaves are all but zero).  Returns
    ``(gap, leaf name)``."""
    names = [n for n in ref if n not in skip]
    floor = statistics.median(ref[n] for n in names)
    worst, where = 0.0, None
    for n in names:
        gap = abs(prog[n] - ref[n]) / max(ref[n], floor, 1e-30)
        if not math.isfinite(gap):
            gap = math.inf
        if where is None or gap > worst:
            worst, where = gap, n
    return worst, where


def nought_leaves(ref_grad_norms, share=1e-3):
    """Leaves whose reference gradient is nought to rounding: under
    ``share`` of the median leaf's (a key's bias under softmax).  Under
    Adam they move by round-off alone, so they are left out of the
    parameter change — by this rule, never by name."""
    floor = share * statistics.median(ref_grad_norms.values())
    return sorted(n for n, g in ref_grad_norms.items() if g < floor)


def verdict(numbers, limits):
    """``numbers`` name -> value; ``limits`` name -> limit.  Returns
    ``(correct, compared)`` where ``compared`` is name ->
    {"value", "limit"} in the limits' order.  A number with no limit, a
    limit with no number, or a value that is not finite, fails."""
    compared, ok = {}, True
    for name, limit in limits.items():
        value = numbers.get(name)
        good = (value is not None and math.isfinite(value)
                and value <= limit)
        ok = ok and good
        compared[name] = {"value": value, "limit": limit}
    for name in numbers:
        if name not in limits:
            ok = False
            compared[name] = {"value": numbers[name], "limit": None}
    return ok, compared
