def read(state, spec):
    share = state["trace"].idle_share()
    return None if share is None else 100.0 * share
