"""Share of scored rows that are bucket padding: ``padded_rows`` over
``scored_rows + padded_rows``, counted over the window."""


def read(run):
    rows = run.stats.get("scored_rows", 0) + run.stats.get("padded_rows", 0)
    return 100.0 * run.stats["padded_rows"] / rows if rows else None
