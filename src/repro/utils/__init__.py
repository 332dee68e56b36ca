"""Shared utilities (hashing, mesh helper, platform config).

Deliberately import-light: ``repro.utils.platform`` must be importable
BEFORE the JAX backend initializes (its whole job is setting XLA flags
that are read once at backend init), so this package must not pull in
modules that create device arrays at import time (``hashing`` builds
``jnp`` constants). Import submodules directly::

    from repro.utils import hashing, compat, platform
"""
