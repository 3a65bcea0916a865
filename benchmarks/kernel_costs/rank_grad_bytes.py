"""What one pass of a ranking objective's gradient program must move,
from shapes alone: the numerator of `rank_grad_roofline`.  Kept with the
benchmark so that no PR that changes the program can change what it is
held against."""


def cost(rows_local, features):
    """A gradient pass must read, once, each local row's score and label
    and write its gradient and hessian (4 bytes each): 16 bytes a row,
    whatever the features.  The pairs of a query are arithmetic on what
    was read, its sort and its max-DCG are small beside the rows, and how
    the program lays queries out (padded buckets, gathers, scatters) is
    its own affair: none of it is counted, so the count is a floor."""
    return 16 * rows_local
