"""Share of the shipped rectangles that is padding."""


def read(obs, params):
    counters = obs["slice_counters"]
    shipped = counters.get("rows_shipped")
    if not shipped or "rows_live" not in counters:
        return None
    live = counters["rows_live"]
    return {"value": 100.0 * (1.0 - live / shipped),
            "note": f"{live:.0f} live rows in {shipped:.0f} shipped cells "
                    f"over the slice"}
