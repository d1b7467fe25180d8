def read(state, spec):
    if not state["peak_bytes"]:
        return None
    return 100.0 * state["peak_bytes"] / state["peak"]["hbm_bytes"]
