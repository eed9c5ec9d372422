"""Share of the window workers' time inside one utils/profile span."""


def read(obs, params):
    spans = obs["profile_spans"]
    if params["span"] not in spans:
        return None
    seconds, _calls = spans[params["span"]]
    base = obs["window_s"] * obs["window_workers"]
    return 100.0 * seconds / base if base > 0 else None
