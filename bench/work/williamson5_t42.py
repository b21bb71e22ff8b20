"""Work of one Williamson case 5 member-horizon (``bench/reference/swe_sphere.py``)."""


def step_flops(fields) -> int:
    """The reference step's float32 operations, grouped by the grid each runs
    on: 125 per cell (the lambda predictor, the corrector, the source at its
    midpoint, the Laplacian's zonal part), 35 per phi face (the phi
    predictor's averages and differences, the corrector's fluxes and the
    Laplacian's meridional fluxes at the ``nlat + 1`` faces) and 9 per
    row-extended cell (the fluxes and pressure of the ``nlat + 2`` rows framed
    by the ghost rows). Ghost-row copies (a row at a time) are not counted."""
    nlon, nlat = fields["nlon"], fields["nlat"]
    cells, faces, extended = nlat * nlon, (nlat + 1) * nlon, (nlat + 2) * nlon
    return 125 * cells + 35 * faces + 9 * extended


def flops(config) -> int:
    return config["steps"] * step_flops(config["fields"])


def hbm_bytes(config) -> int:
    field = config["fields"]["nlon"] * config["fields"]["nlat"] * 4
    snapshots = config["steps"] // config["snapshot_every"]
    return 3 * field + snapshots * field + 3 * field
