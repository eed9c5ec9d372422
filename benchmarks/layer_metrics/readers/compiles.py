"""Executables asked for inside the window."""


def read(obs, params):
    return {"value": float(obs["compile_requests"]),
            "note": f"{obs['compile_requests'] - obs['compile_hits']} built, "
                    f"{obs['compile_hits']} loaded from the persistent cache"}
