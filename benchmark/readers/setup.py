def read(state, spec):
    return state["setup_s"]
