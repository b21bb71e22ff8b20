"""Validation of the trip-count-aware HLO cost rollup (launch/hlo_cost.py)
against programs with hand-computable costs — the measurement layer behind
EXPERIMENTS.md §Roofline."""

import jax
import jax.numpy as jnp
import pytest

from repro.launch.hlo_cost import parse_hlo_costs

MM_FLOPS = 2 * 256 * 512 * 512  # one (256,512)x(512,512) matmul


def _compile_text(f, *sds):
    return jax.jit(f).lower(*sds).compile().as_text()


X = jax.ShapeDtypeStruct((256, 512), jnp.float32)
W = jax.ShapeDtypeStruct((512, 512), jnp.float32)


class TestTripCounts:
    @pytest.mark.parametrize("L", [1, 4, 16, 64])
    def test_scan_multiplies_body_cost(self, L):
        def f(x, w):
            def body(c, _):
                return jnp.tanh(c @ w), None

            y, _ = jax.lax.scan(body, x, None, length=L)
            return y

        cost = parse_hlo_costs(_compile_text(f, X, W))
        assert cost["flops"] == pytest.approx(L * MM_FLOPS, rel=0.01)

    def test_nested_scan(self):
        def f(x, w):
            def body(c, _):
                return jnp.tanh(c @ w), None

            def outer(c, _):
                c, _ = jax.lax.scan(body, c, None, length=4)
                return c, None

            y, _ = jax.lax.scan(outer, x, None, length=4)
            return y

        cost = parse_hlo_costs(_compile_text(f, X, W))
        assert cost["flops"] == pytest.approx(16 * MM_FLOPS, rel=0.01)

    def test_naive_cost_analysis_misses_trips(self):
        """Documents WHY this module exists: XLA counts loop bodies once."""

        def f(x, w):
            def body(c, _):
                return jnp.tanh(c @ w), None

            y, _ = jax.lax.scan(body, x, None, length=16)
            return y

        compiled = jax.jit(f).lower(X, W).compile()
        naive = compiled.cost_analysis().get("flops", 0.0)
        assert naive < 2 * MM_FLOPS  # counts ~1 matmul, not 16
        corrected = parse_hlo_costs(compiled.as_text())["flops"]
        assert corrected == pytest.approx(16 * MM_FLOPS, rel=0.01)


class TestBytesModel:
    def test_scan_bytes_near_hand_model(self):
        # VMEM-resident small operands charged once per loop entry; per-iter
        # traffic = dot result (.5M) + tanh fusion (.5M) = 1MB x 16 iters,
        # plus one residency charge for x and w (~1.5M) ~ 17.5MB.
        def f(x, w):
            def body(c, _):
                return jnp.tanh(c @ w), None

            y, _ = jax.lax.scan(body, x, None, length=16)
            return y

        cost = parse_hlo_costs(_compile_text(f, X, W))
        assert 8e6 < cost["bytes"] < 48e6


class TestCollectives:
    def test_collective_inside_scan_multiplied(self):
        import os
        import subprocess
        import sys

        code = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.launch.hlo_cost import parse_hlo_costs
mesh = jax.make_mesh((8,), ("model",))
def f(x, w):
    def body(c, _):
        y = c @ w  # w sharded on the contracting dim -> all-reduce per iter
        return jax.lax.with_sharding_constraint(jnp.tanh(y), NamedSharding(mesh, P())), None
    out, _ = jax.lax.scan(body, x, None, length=5)
    return out
x = jax.ShapeDtypeStruct((256, 512), jnp.float32)
w = jax.ShapeDtypeStruct((512, 512), jnp.float32)
with mesh:
    c = jax.jit(f, in_shardings=(NamedSharding(mesh, P()), NamedSharding(mesh, P("model", None)))).lower(x, w).compile()
cost = parse_hlo_costs(c.as_text())
ar = cost["collective_bytes"].get("all-reduce", 0)
expect = 5 * 256 * 512 * 4
assert abs(ar - expect) / expect < 0.01, (ar, expect)
print("COLL_OK", ar)
"""
        env = dict(os.environ, PYTHONPATH="src")
        env.pop("XLA_FLAGS", None)
        r = subprocess.run(
            [sys.executable, "-c", code],
            capture_output=True,
            text=True,
            env=env,
            cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        )
        assert "COLL_OK" in r.stdout, r.stderr[-1500:]
