"""One registry counter over another (`waves_total` / `trees_grown`), as
the program counted them in the process that trained.  Either missing or
zero returns nothing."""

from benchmarks.reducers import program_total


def reduce(ctx, num, den):
    have = program_total.totals("counter")
    if not have.get(num) or not have.get(den):
        return None
    return have[num] / have[den]
