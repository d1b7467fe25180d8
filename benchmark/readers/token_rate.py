"""Output tokens by the stamp of the dispatch that delivered them (a
decode block's fetch, or a prefill's first token), over the window's
whole length."""
from benchmark.readers import in_window


def read(state, spec):
    tokens = sum(e["attrs"]["tokens"] for e in in_window(state)
                 if e["name"] == "dispatch")
    return tokens / (state["t_close"] - state["t_open"])
