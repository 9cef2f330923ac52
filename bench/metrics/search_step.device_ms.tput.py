"""Device time of one run of the served search step."""

from readers import step_device_ms as read  # noqa: F401
