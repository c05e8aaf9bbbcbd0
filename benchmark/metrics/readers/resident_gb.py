"""Corpus bytes resident on the device (`telemetry.device_memory`,
class `corpus_columns`), GB."""


def read(run, params):
    classes = run.stats["after"]["telemetry"]["device_memory"]["classes"]
    return classes.get("corpus_columns", {}).get("live_bytes", 0) / 1e9
