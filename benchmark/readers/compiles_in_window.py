def read(state, spec):
    return state["compile_log"].between(state["t_open"], state["t_close"])
