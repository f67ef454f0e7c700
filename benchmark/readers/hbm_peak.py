"""Peak device memory in use over the device's limit, fullest chip."""


def read(run, spec):
    device = run["device"]
    if not device.get("memory_limit_bytes"):
        return None
    return 100.0 * device["memory_peak_bytes"] / device["memory_limit_bytes"]
