"""Plain float32 ``jax.numpy`` references, one module per stepper.

Each is written from the equations of the paper's workload and imports
nothing of the program under test. A reference module exposes
``initial_state(cfg, scales)`` (the benchmark builds every input with it,
so the program and the reference start from the same arrays), ``run(cfg,
state0, steps, every)`` and ``observable(state)``.
"""
