"""Bytes of one class of `telemetry.device_memory` after the window,
GB: with `of` "fullest_device" the most any one device holds of it
(`by_device`), else the class's `live_bytes`. None where the node
reports no such class (nothing of it was ever resident)."""


def read(run, params):
    classes = run.stats["after"]["telemetry"]["device_memory"]["classes"]
    row = classes.get(params["class"])
    if row is None:
        return None
    if params.get("of") == "fullest_device":
        by_device = row.get("by_device") or {}
        return max(by_device.values()) / 1e9 if by_device else None
    return row.get("live_bytes", 0) / 1e9
