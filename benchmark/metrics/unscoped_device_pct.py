"""Share of the leaf device time of the traced whole steps whose scope
path names none of the regions: what no layer of the program owns.  It
reads nothing where the compiled step names no region at all."""

from benchmark.work import regions


def read(ctx):
    table = regions.region_table(ctx)
    if table is None:
        return None
    rows = table["regions"]
    if not any(rows[name][0] > 0 for name in regions.REGIONS):
        return None
    return 100.0 * rows[regions.UNSCOPED][0] / table["seconds"]
