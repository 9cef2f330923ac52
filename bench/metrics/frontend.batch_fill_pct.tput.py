"""Live rows over padded rows, over every dispatch of the loop."""

from readers import batch_fill_pct as read  # noqa: F401
