"""Small sizes of the benchmark's configurations for runs on the CPU."""

import types

from bench import harness

SMALL = {
    "swe2d_128": dict(fields={"nx": 16, "ny": 16}, steps=40, snapshot_every=10),
    "heat1d_128": dict(fields={"nx": 32}, steps=40, snapshot_every=10),
}


def small_loader(members=4, rate=20.0, **config_changes):
    """``harness.load_json`` with the configurations cut to ``SMALL`` (and
    ``config_changes``), ensembles to ``members`` and the served rate to
    ``rate``."""
    real = harness.load_json

    def load(kind, name, bench=harness.BENCH):
        d = real(kind, name, bench)
        if kind == "configs":
            cut = dict(SMALL[name], **config_changes.get(name, {}))
            d["fields"].update(cut.pop("fields"))
            d.update(cut)
        elif d["kind"] == "ensemble":
            d["members"] = members
        else:
            d["rate"] = rate
        return d

    return load


def run(monkeypatch, workload, seconds=1.0, control=False, seed=2**31 + 17, **loader):
    """One run of ``workload`` on the CPU through everything after the look
    for a chip. Returns the result line."""
    import jax

    monkeypatch.setattr(harness, "load_json", small_loader(**loader))
    spec = harness.load_spec()
    chips = harness.find_cell(spec, workload)["chips"]
    args = types.SimpleNamespace(workload=workload, seed=seed, seconds=seconds,
                                 trace=0, control=control)
    return harness.run_cell(args, spec, jax.devices()[:chips], 0.0)
