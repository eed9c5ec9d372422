"""Events taken in / time until their last result reached the sink."""


def read(obs, params):
    if obs["window_s"] <= 0 or not obs["events_in"]:
        return None
    return obs["events_in"] / obs["window_s"]
