"""How well the host spans and the device ops of the traced slice were
laid on one clock (benchmark/spans.py `join_planes`). `what`:
`bracket_us`, the width of the feasible interval of the clock offset,
the intersection over the planes (the error bar of every attribution
made with it), us;
`runs_agree`, the check of the matching against what the bracket did
not use (`runs_agree`): the share of matched device time in runs that
take what the executable their wave dispatched takes in its other runs,
on the plane that agrees least, %. Well under 100: the k-th wave was
given another wave's run."""

from benchmark import spans


def read(run, params):
    mesh = spans.device_join(run)
    if mesh is None:
        return None
    if params["what"] == "bracket_us":
        return mesh.bracket_ns / 1e3
    share = spans.mesh_runs_agree(mesh)
    return None if share is None else 100.0 * share
