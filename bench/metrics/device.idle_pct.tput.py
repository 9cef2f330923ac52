"""Share of the measured window in which no op ran on the chip(s)."""

from readers import idle_pct as read  # noqa: F401
