"""The harness: discovery by file name, the result line, and the refusal to
run without a chip."""

import json
import os
import re
import subprocess
import sys
from pathlib import Path

import pytest

from bench import harness

ROOT = Path(__file__).resolve().parents[2]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@pytest.fixture(scope="module")
def spec():
    return harness.load_spec()


def test_every_name_in_the_spec_resolves_to_its_files(spec):
    for cfg in spec["configs"]:
        assert (ROOT / cfg["file"]).is_file()
        assert cfg["file"] == f"bench/configs/{cfg['name']}.json"
        config = harness.load_json("configs", cfg["name"])
        harness.load_module("work", cfg["name"])
        harness.load_module("reference", config["stepper"])
        assert config["reduced"] == cfg["reduced"]
    for cell in spec["workloads"]:
        mix = harness.load_json("traffic", cell["traffic"])
        driver = harness.load_module("traffic", mix["kind"])
        assert hasattr(driver, "Run")
        assert cell["config"] in {c["name"] for c in spec["configs"]}
    for m in spec["per_layer"]:
        assert callable(harness.load_module("metrics", m["name"]).read)


def test_spec_shape(spec):
    assert set(spec) == {"command", "paths", "run_seconds", "configs", "workloads",
                         "end_to_end", "per_layer"}
    names = [x["name"] for k in ("configs", "workloads", "end_to_end", "per_layer")
             for x in spec[k]]
    assert all(NAME.match(n) for n in names)
    assert len({c["name"] for c in spec["workloads"]}) == len(spec["workloads"])
    pairs = [(c["config"], c["traffic"]) for c in spec["workloads"]]
    assert len(set(pairs)) == len(pairs)
    e2e = {m["name"] for m in spec["end_to_end"]}
    assert "setup_s" in e2e
    for m in spec["per_layer"]:
        assert m["moves"] in e2e
        moved = next(x for x in spec["end_to_end"] if x["name"] == m["moves"])
        for cell in m["workloads"]:
            assert cell in moved.get("workloads", [cell])
    for cell in spec["workloads"]:
        assert harness.metrics_of(spec, "per_layer", cell["name"])
        assert len(harness.metrics_of(spec, "end_to_end", cell["name"])) >= 2


def test_discovery_by_file_name(tmp_path):
    """A metric, a mix and a configuration added as files are found by name."""
    (tmp_path / "metrics").mkdir()
    (tmp_path / "metrics" / "new.layer_metric.py").write_text(
        "def read(ctx):\n    return ctx.counts.get('n')\n")
    (tmp_path / "traffic").mkdir()
    (tmp_path / "traffic" / "new_mix.json").write_text('{"kind": "ensemble", "members": 3}')
    (tmp_path / "configs").mkdir()
    (tmp_path / "configs" / "new_cfg.json").write_text('{"stepper": "heat1d"}')
    reader = harness.load_module("metrics", "new.layer_metric", bench=tmp_path)
    assert reader.read(type("Ctx", (), {"counts": {"n": 7}})) == 7
    assert harness.load_json("traffic", "new_mix", bench=tmp_path)["members"] == 3
    assert harness.load_json("configs", "new_cfg", bench=tmp_path)["stepper"] == "heat1d"
    with pytest.raises(FileNotFoundError):
        harness.load_module("metrics", "absent", bench=tmp_path)


def test_metrics_of_follows_workloads_key():
    spec = {"per_layer": [{"name": "a", "workloads": ["x"]}, {"name": "b"}]}
    assert [m["name"] for m in harness.metrics_of(spec, "per_layer", "x")] == ["a", "b"]
    assert [m["name"] for m in harness.metrics_of(spec, "per_layer", "y")] == ["b"]


def test_result_line_schema():
    line = harness.result_line(
        correct=True, attempted=10, failed=0,
        metrics={"setup_s": {"value": 1.5, "unit": "s"}},
        device={"platform": "tpu", "kind": "TPU v5 lite", "count": 1, "memory_peak_bytes": 3},
        checks=[("rel_l2_max", 0.1, 0.5), ("broken", float("inf"), 1.0)],
        breakdown={"device_ops": [["k", 1.0]], "idle_gaps": [["bench.wait", 0.5]]},
    )
    assert list(line) == ["correct", "attempted", "failed", "metrics", "device",
                          "breakdown", "checks"]
    assert line["checks"]["rel_l2_max"] == {"value": 0.1, "limit": 0.5}
    assert line["checks"]["broken"]["value"] == "inf"
    assert json.loads(json.dumps(line, allow_nan=False)) == line
    plain = harness.result_line(correct=False, attempted=1, failed=1, metrics={},
                                device={}, checks=[])
    assert "breakdown" not in plain and list(plain)[-1] == "checks"


def _bench(*args, cwd=ROOT):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "-m", "bench", *args], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=300,
    )


def test_command_refuses_to_run_without_a_tpu():
    out = _bench("--workload", "swe2d.ens51", "--seed", "3000000000", "--seconds", "1",
                 "--trace", "0")
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "no TPU" in out.stderr


def test_unknown_workload_fails():
    out = _bench("--workload", "no.such.cell", "--seed", "1", "--seconds", "1")
    assert out.returncode != 0
    assert out.stdout.strip() == ""
