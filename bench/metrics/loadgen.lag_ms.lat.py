"""Worst lateness of the open-loop generator in the window."""

from readers import lag_ms as read  # noqa: F401
