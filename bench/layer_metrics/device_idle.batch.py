"""Device layer, batch cells: the share of the traced window in which no
operation ran on the chip (1 - busy / window), in %."""

from trace_reduce import idle_percent as read  # noqa: F401
