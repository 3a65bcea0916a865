"""A total the program keeps of itself, read in the process that trained:
`kind` "timer" sums the seconds of the `global_timer` scopes `names`
(`Dataset::find_bin`), `kind` "counter" the registry counters `names`
(`first_iter_jit_trace_s`).  They are totals of the process, whatever it
ran; a counter the program snapshots itself (`first_iter_*`: around a
booster's first iteration) holds that part alone.
A program that keeps none of them (the parent of the PR that added
them), or a process that has not trained, returns nothing."""


def totals(kind):
    """{name: number} of the program's own totals, {} where it has none."""
    try:
        if kind == "timer":
            from lightgbm_tpu.utils.timer import global_timer
            return {k: v[0] for k, v in global_timer.snapshot().items()}
        from lightgbm_tpu.observability import global_registry
        return dict(global_registry.snapshot()["counters"])
    except Exception:   # noqa: BLE001 - whatever the program lacks
        return {}


def reduce(ctx, kind, names):
    have = totals(kind)
    found = [have[n] for n in names if n in have]
    total = float(sum(found))
    return total if found and total > 0 else None
