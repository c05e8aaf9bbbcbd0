"""One of the harness's own spans, in seconds: `setup_s` (process start
to the first timed request), `install_upload_s`, `warmup_s`, ..."""


def read(run, params):
    return run.spans.get(params["name"])
