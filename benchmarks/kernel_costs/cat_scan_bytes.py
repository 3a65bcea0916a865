"""What an iteration's categorical split scans must move, from the plan's
counts alone: the numerator of `cat_scan_roofline`.  Kept with the
benchmark so that whatever later implements the scan is held to the same
work."""


def cost(num_leaves, program_counters):
    """Growing a tree of `num_leaves` leaves scans 2 x num_leaves - 1
    leaves (the root and both children of every split).  Each scan must
    read the leaf's categorical columns' bins (`cat_bins`: their
    `num_bin` summed) once, a float32 gradient and hessian each: 8 bytes
    a bin.  Padding to `max_bin`, the wave engine's extra scans, the
    sort's own traffic and the index arithmetic are the program's own
    affair: none of it is counted, so the count is a floor.  No plan
    counted (a program from before the counter, a run without
    categorical columns) returns nothing."""
    bins = program_counters.get("cat_bins", 0)
    if not bins or not num_leaves:
        return None
    return (2 * num_leaves - 1) * bins * 8
