"""Traffic: one driver module per kind (``<kind>.py``) and one data file
per mix (``<mix>.json``, whose ``kind`` names its driver).

A driver module defines ``Run(config=, mix=, seed=, seconds=, devices=,
control=)`` with ``setup()``, ``window()``, ``end_to_end()``, ``free()`` and
``check()``, and the attributes ``counts``, ``attempted`` and ``failed``.
"""
