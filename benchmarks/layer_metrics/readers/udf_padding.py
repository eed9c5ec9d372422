"""Share of the cells a user's window function ran over that held no point
of a window: the library pads each window to the bucketed longest length and
each batch to a bucketed count (``udf_cells``); ``udf_rows`` are the points
the windows really held.  A program without the counters reports nothing."""


def read(obs, params):
    counters = obs["slice_counters"]
    cells = counters.get("udf_cells")
    if not cells or "udf_rows" not in counters:
        return None
    rows = counters["udf_rows"]
    return {"value": 100.0 * (1.0 - rows / cells),
            "note": f"{rows:.0f} points in {cells:.0f} cells of "
                    f"{counters.get('udf_windows', 0):.0f} windows over the "
                    f"slice; an all-pairs function pays the square of it"}
