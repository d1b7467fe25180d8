"""The median idle gap on the device between two consecutive programs
whose name matches ``module``, in ms."""
from benchmark.readers import median


def read(state, spec):
    mods = state["trace"].module_events(spec["module"])
    return median([(b[0] - a[1]) / 1e6 for a, b in zip(mods, mods[1:])
                   if b[0] > a[1]])
