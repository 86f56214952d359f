"""Kernels: share of the paged decode kernel's grid pages (slots x block
table width, per executed decode step) that hold a live position, up to
each slot's cache_len, over the window, in %: the part of the grid the
kernel still reads and computes."""


def read(run):
    if "decode_pages_grid" not in run.stats["end"]:
        return None
    grid = run.delta("decode_pages_grid")
    if grid <= 0:
        return None
    return 100.0 * run.delta("decode_pages_live") / grid
