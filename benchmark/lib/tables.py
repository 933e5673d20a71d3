"""Host tables onto the device, and waiting for device tables."""

from __future__ import annotations


def from_host(env, host: dict) -> dict:
    """``{name: ct.Table}`` from ``{name: {column: array}}`` through the
    public ingest (``ct.Table.from_pydict``: pads to the shape family)."""
    import cylon_tpu as ct
    return {name: ct.Table.from_pydict(cols, env)
            for name, cols in host.items()}


def ready(*tables) -> None:
    """Returns when every column (data and validity) of ``tables`` is ready
    on the device."""
    import jax
    jax.block_until_ready([(c.data, c.validity) for t in tables
                           for c in t.columns.values()])
