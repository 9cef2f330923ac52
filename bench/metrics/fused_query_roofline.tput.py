"""The fused gather-score-top-m kernel's share of its roofline."""

from readers import fused_query_roofline as read  # noqa: F401
