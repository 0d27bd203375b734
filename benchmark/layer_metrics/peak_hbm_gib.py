"""Peak bytes held on the fullest device, GiB: the device's own
`peak_bytes_in_use` + `peak_bytes_reserved` (harness/device.py)."""


def read(run):
    peak = run.facts.get("peak_bytes")
    return None if peak is None else peak / 2.0 ** 30
