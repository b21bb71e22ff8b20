"""Work of one heat1d member-horizon (``bench/reference/heat1d.py``)."""


def step_flops(fields) -> int:
    # lap: scale, subtract, add; alpha * lap; * dt/dx^2; u + upd
    return 6 * (fields["nx"] - 2)


def flops(config) -> int:
    return config["steps"] * step_flops(config["fields"])


def hbm_bytes(config) -> int:
    field = config["fields"]["nx"] * 4
    snapshots = config["steps"] // config["snapshot_every"]
    return field + snapshots * field + field
