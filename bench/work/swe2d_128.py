"""Work of one swe2d member-horizon (``bench/reference/swe2d.py``)."""


def _flux_ops(n: int) -> int:
    # F(U) or G(U) at n points: hu*hv/h (2), q*q/h + 0.5*g*h*h (5)
    return 7 * n


def step_flops(fields) -> int:
    nx, ny = fields["nx"], fields["ny"]
    full = nx * ny
    xmid, ymid = (nx - 1) * ny, nx * (ny - 1)
    inner = (nx - 2) * (ny - 2)
    return (
        2 * _flux_ops(full)  # F(U), G(U)
        + 3 * 5 * xmid  # Ux: add, halve, difference, scale, subtract per field
        + 3 * 5 * ymid  # Uy
        + _flux_ops(xmid)  # F(Ux)
        + _flux_ops(ymid)  # G(Uy)
        + 3 * 6 * inner  # interior: two differences, two scales, two subtractions
    )


def flops(config) -> int:
    return config["steps"] * step_flops(config["fields"])


def hbm_bytes(config) -> int:
    field = config["fields"]["nx"] * config["fields"]["ny"] * 4
    snapshots = config["steps"] // config["snapshot_every"]
    return 3 * field + snapshots * field + 3 * field
