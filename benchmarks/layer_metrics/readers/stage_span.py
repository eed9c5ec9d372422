"""Share of one window stage's workers' time inside one utils/profile span,
the stage's worker count being a key of the configuration's ``shapes``
(``profile_span`` divides by the device window workers, which a host stage is
not among).  A program without the span reports nothing."""


def read(obs, params):
    spans = obs["profile_spans"]
    if params["span"] not in spans:
        return None
    seconds, calls = spans[params["span"]]
    workers = int(obs["cfg"]["shapes"][params["workers"]])
    base = obs["window_s"] * workers
    if base <= 0:
        return None
    return {"value": 100.0 * seconds / base,
            "note": f"{seconds:.4f} s in {calls} calls over {workers} workers"}
