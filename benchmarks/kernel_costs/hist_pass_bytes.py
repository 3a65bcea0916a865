"""What one histogram pass must move, from shapes alone: the numerator
of the kernel's roofline share.  Kept with the benchmark so that no PR
that changes a kernel can change what it is held against; a new kernel's
count is a new file here, named by its metric under `bytes_fn`."""


def cost(rows_local, features):
    """A histogram pass must read, once, each local row's bins (one byte
    a feature) and its gradient, hessian and slot (4 bytes each).  The
    histogram it writes is small beside that and is left out, so the
    count is a floor."""
    return rows_local * (features + 12)
