"""Algorithmic work of one horizon, one module per configuration.

Counted from the equations and the shapes, not from any implementation, so a
kernel's roofline reads the same work whichever plane computes it. Each
module gives ``flops(config)`` (float32 operations of one member over the whole
horizon) and ``hbm_bytes(config)`` (the least bytes one member's horizon must
move: its initial state read once, every snapshot and the final state
written once).
"""
