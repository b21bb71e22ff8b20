"""Open loop of single-member requests through ``repro.service.SimService``.

``round(rate * seconds)`` requests are due in the window. Their gaps are the
quantiles of an exponential distribution of mean ``1 / rate`` (Poisson
arrivals), put in an order drawn from the seed, so that every seed offers the
same gaps and the same work. Each request is one member of the configured
horizon and cadence with its own initial state: the reference module's
``initial_state`` at a scale taken from ``scale`` evenly and put in an order
drawn from the seed. The configuration is the same for all, so every request
shares one bucket key.

The loop submits every request that is due, then pumps the service once,
and sleeps only when the service is idle. A request's latency runs from the
time it was due to the pump that finished it. After the last arrival the
service is drained, for at most ``grace_s`` seconds; a request refused by
backpressure or not done by then has failed.

Set-up submits 1, 2, ... ``max_bucket`` requests in turn and drains each
group, so every bucket width that the window can reach is compiled.

Mix keys: ``rate`` (requests per second), ``scale`` ([lo, hi]),
``precision``, ``control``, ``execution``, ``grace_s`` and ``limit`` (of
``rel_l2_max``).
"""

from __future__ import annotations

import importlib
import time

import jax
import numpy as np
from jax.profiler import TraceAnnotation

from bench import compare, program


class Run:
    def __init__(self, *, config, mix, seed, seconds, devices, control=False):
        self.config, self.mix, self.seed, self.seconds = config, mix, seed, seconds
        self.devices, self.control = list(devices), control
        self.fields = config["fields"]
        self.steps, self.every = config["steps"], config["snapshot_every"]
        self.ref = importlib.import_module(f"bench.reference.{config['stepper']}")
        self.counts: dict = {}
        self.attempted = self.failed = 0

    def _request(self, state0):
        from repro.service import SimRequest

        return SimRequest(
            self.config["stepper"], steps=self.steps, precision=self.prec,
            cfg=self.pcfg, snapshot_every=self.every,
            execution=self.mix["execution"], state0=state0,
        )

    def setup(self):
        from repro.service import ServiceConfig, SimService
        from repro.service.metrics import ServiceMetrics

        self.prec = program.precision(self.mix["control" if self.control else "precision"])
        self.pcfg = program.program_config(self.config)
        n = max(1, round(self.mix["rate"] * self.seconds))
        rng = program.seed_rng(self.seed, 2)
        q = (np.arange(n) + 0.5) / n
        gaps = rng.permutation(-np.log1p(-q) / self.mix["rate"])
        self.due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
        lo, hi = self.mix["scale"]
        self.scales = rng.permutation(np.linspace(lo, hi, n))
        states = jax.jit(lambda s: self.ref.initial_state(self.fields, s))(self.scales)
        self.states = list(states)
        self.svc = SimService(ServiceConfig())
        warm = self.states[0]
        for width in range(1, self.svc.config.max_bucket + 1):
            for _ in range(width):
                self.svc.submit(self._request(warm))
            self.svc.run_until_idle()
        self.svc.metrics = ServiceMetrics()  # the window's chunks only

    def window(self):
        from repro.service import ServiceOverloaded

        svc, due, n = self.svc, self.due, len(self.due)
        self.handles = [None] * n
        done_at = np.full(n, np.nan)
        inflight, i, refused = [], 0, 0
        t0 = time.perf_counter()
        deadline = t0 + due[-1] + self.mix["grace_s"]
        late = 0.0
        while True:
            now = time.perf_counter() - t0
            while i < n and due[i] <= now:
                late = max(late, now - due[i])
                try:
                    with TraceAnnotation("bench.submit"):
                        h = svc.submit(self._request(self.states[i]))
                    self.handles[i] = h
                    inflight.append(i)
                except ServiceOverloaded:
                    refused += 1
                i += 1
            if inflight:
                with TraceAnnotation("bench.pump"):
                    svc.pump()
                t = time.perf_counter() - t0
                still = []
                for j in inflight:
                    if self.handles[j].status in ("done", "failed"):
                        done_at[j] = t
                    else:
                        still.append(j)
                inflight = still
            elif i < n:
                time.sleep(max(0.0, due[i] - (time.perf_counter() - t0)))
            if (i == n and not inflight) or time.perf_counter() > deadline:
                break
        end = time.perf_counter() - t0
        ok = np.array([h is not None and h.status == "done" for h in self.handles])
        latency = np.where(ok, done_at - due, end - due)  # a failure waited to the end
        self.attempted, self.failed = n, int(n - ok.sum())
        m = svc.metrics
        self.counts = dict(
            requests=n,
            refused=refused,
            latency_s=latency,
            window_s=end,
            generator_late_s=late,
            chunk_ms_p50=m.latency_us(50) / 1e3,
            compiles=m.compiles,
            chunks=m.chunks,
        )
        return self.counts

    def end_to_end(self):
        return {"request_ms_p50": float(np.percentile(self.counts["latency_s"], 50) * 1e3)}

    def free(self):
        self.results = [
            None if h is None or h.status != "done" else h.result() for h in self.handles
        ]
        del self.svc, self.handles, self.states

    def check(self):
        steps, every = self.steps, self.every
        idx = [j for j, r in enumerate(self.results) if r is not None]
        ref = jax.jit(jax.vmap(lambda s: self.ref.run(self.fields, s, steps, every)))
        gen = jax.jit(lambda s: self.ref.initial_state(self.fields, s))
        worst = 0.0
        if idx:
            ref_final, ref_snaps = ref(gen(self.scales[idx]))
            final = np.stack([np.asarray(self.results[j].state) for j in idx])
            snaps = np.stack([np.stack(self.results[j].snapshots) for j in idx])
            gaps = compare.worst_member_gap(
                final, snaps, ref_final, ref_snaps, self.ref.offsets(self.fields)
            )
            worst = float(gaps.max())
        self.counts["compared"] = len(idx)
        return [
            ("rel_l2_max", worst, self.mix["limit"]),
            ("requests_not_done", self.failed, 0),
        ]
