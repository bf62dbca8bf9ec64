"""The program's own `session.*` counters, read in the run's process: the
process-wide registry (`repro.obs.registry()`) into which the search
session records each batch it dispatches and lands. They count every
batch of the run, the warm-up batches of set-up included, so readers
take ratios of them. A program that keeps no such counters gives an
empty mapping."""

from __future__ import annotations


def counters() -> dict:
    """{counter name without `session.`: value}; {} where the program
    has no process-wide registry."""
    try:
        from repro import obs
    except ImportError:
        return {}
    reg = getattr(obs, "registry", None)
    if reg is None:
        return {}
    return {k[len("session."):]: v for k, v in reg().snapshot().items()
            if k.startswith("session.")}


def ratio(c: dict, num: str, den: str) -> float | None:
    """c[num] / c[den]; None where either is absent or zero."""
    n, d = c.get(num), c.get(den)
    return n / d if n and d else None
