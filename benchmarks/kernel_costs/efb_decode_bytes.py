"""What an iteration's EFB decodes must move, from the plan's counts
alone: the numerator of `efb_decode_roofline`.  Kept with the benchmark
so that whatever later implements the decode is held to the same work."""


def cost(num_leaves, program_counters):
    """Growing a tree of `num_leaves` leaves scans 2 x num_leaves - 1
    leaves (the root and both children of every split).  Each scan must
    read the leaf's bundle-column histogram (`efb_bundle_bins` bins in
    all) and write its members' per-feature bins (`efb_member_bins`)
    once, a float32 gradient and hessian each: 8 bytes a bin.  Padding a
    2-bin feature to `max_bin`, scanning the overgrown tree's extra
    leaves or every leaf every wave, and the index arithmetic are the
    program's own affair: none of it is counted, so the count is a
    floor.  No plan counted (a program from before the counters, a run
    without bundles) returns nothing."""
    bins = (program_counters.get("efb_bundle_bins", 0)
            + program_counters.get("efb_member_bins", 0))
    if not bins or not num_leaves:
        return None
    return (2 * num_leaves - 1) * bins * 8
