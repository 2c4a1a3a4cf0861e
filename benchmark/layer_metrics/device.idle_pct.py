"""Share of the traced span in which no operation ran on the device."""

SPEC = {"layer": "device (one TPU v5e)", "unit": "%",
        "source": "device_trace", "moves": "deliveries_per_s"}


def read(run: dict):
    tr = run["trace"]
    if tr is None or not tr["device_planes"] or not tr["window_s"]:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
