"""What one histogram call must multiply, from the table alone: the
numerator of `hist_mxu_roofline` and the denominator of
`hist_mxu_padding`.  Kept with the benchmark so that no PR that changes a
kernel, a class or a padding can change what the kernels are held
against."""


def cost(rows_local, hist_codes, columns):
    """A one-hot-matmul histogram of a wave multiplies, for every local
    row, each code of each device column (`hist_codes`: the sum of the
    columns' own code counts, the program's registry counter) into each
    useful output column (`columns`: channels x the wave's TRUE computed
    slots): 2 FLOP a product.  Whatever kernel, class, feature group or
    tile padding implements it is the program's own affair and is not
    counted, so the count is a floor.  Nothing counted returns
    nothing."""
    if not rows_local or not hist_codes or not columns:
        return None
    return 2 * rows_local * hist_codes * columns
