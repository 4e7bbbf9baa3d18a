"""Host seconds per save in the device fingerprint stage
(``DedupCheckpointer._batch_device_fps``: the wave planner, the device
waves and their ``device_get``), from the benchmark's span around it."""


def read(ctx):
    saves = ctx["spans"].get("save.write", [])
    if not saves:
        return None
    return sum(ctx["spans"].get("save.waves", [])) / len(saves)
