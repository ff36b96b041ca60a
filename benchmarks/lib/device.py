"""The device a run is on, and the table of peaks."""
import json
import os


class DeviceError(Exception):
    pass


def peaks_table(root):
    with open(os.path.join(root, "benchmarks", "peaks.json")) as f:
        return json.load(f)["peaks"]


def describe(chips, root, rehearse):
    """(device dict for the result line, peaks of that kind). No chip,
    too few chips or a kind the table lacks is an error."""
    import jax
    devs = jax.devices()
    dev = devs[0]
    info = {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devs)}
    if rehearse:
        return info, None
    if dev.platform == "cpu":
        raise DeviceError("JAX found no accelerator (platform %r)"
                          % dev.platform)
    if len(devs) < chips:
        raise DeviceError("the cell needs %d chips, JAX sees %d"
                          % (chips, len(devs)))
    table = peaks_table(root)
    if dev.device_kind not in table:
        raise DeviceError("device_kind %r is not in benchmarks/peaks.json"
                          % dev.device_kind)
    return info, table[dev.device_kind]


def memory_peak_bytes(log=None):
    """Peak bytes taken on the fullest chip: the most that was in use as
    buffers plus the most that loaded programs held reserved for their
    temporaries (the TPU backend counts the two apart, and a training
    step's activations are all in the second). 0 where the backend
    reports nothing, as on the CPU."""
    import jax
    peak = 0
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0))
                   + int(stats.get("peak_bytes_reserved", 0)))
        if log is not None and stats:
            log("memory of %s: %r" % (d, stats))
    return peak
