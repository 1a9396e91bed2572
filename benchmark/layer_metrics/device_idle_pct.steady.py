from _common import device_idle_pct as read  # noqa: F401
