"""The chip benchmark's harness: everything that is not one configuration,
one traffic mix or one per-layer metric (those are files of their own under
``configs/``, ``traffic/`` and ``metrics/``, found by name)."""
