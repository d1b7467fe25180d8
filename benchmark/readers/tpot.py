"""Time per output token: for every request, the gap between two of its
consecutive decode blocks' fetches over the tokens the later one gave it;
the median over the window."""
from benchmark.readers import in_window, median


def read(state, spec):
    last, sample = {}, []
    for e in in_window(state):
        if e["name"] != "decode" or e.get("span_name") != "request":
            continue
        span, n = e["span"], e["attrs"]["tokens"]
        if span in last and n:
            sample.append((e["t"] - last[span]) * 1e3 / n)
        last[span] = e["t"]
    return median(sample)
