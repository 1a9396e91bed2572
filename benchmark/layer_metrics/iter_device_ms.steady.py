from _common import iter_device_ms as read  # noqa: F401
