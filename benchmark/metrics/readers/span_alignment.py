"""How well the host spans and the device ops of the traced slice were
laid on one clock (benchmark/spans.py `join`). `what`:
`bracket_us`, the width of the feasible interval of the clock offset
(the error bar of every attribution made with it), us;
`runs_agree`, the check of the matching against what the bracket did
not use (`runs_agree`): the share of matched device time in runs that
take what the executable their wave dispatched takes in its other runs,
%. Well under 100: the k-th wave was given another wave's run."""

from benchmark import spans


def read(run, params):
    jn = spans.device_join(run)
    if jn is None:
        return None
    if params["what"] == "bracket_us":
        return jn.bracket_ns / 1e3
    share = spans.runs_agree(jn)
    return None if share is None else 100.0 * share
