"""Delta-sum over delta-count of the node's histograms over the window:
the means of `plus` summed, less the means of `minus`, ms."""

from benchmark import readings


def read(run, params):
    total = 0.0
    for sign, names in ((1.0, params.get("plus", [])),
                        (-1.0, params.get("minus", []))):
        for name in names:
            mean = readings.hist_delta_mean(run, name)
            if mean is None:
                return None
            total += sign * mean
    return total
