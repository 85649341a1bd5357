"""Share of the traced window in which no operation ran on the device,
averaged over the cell's devices."""
from readings import idle_pct as read  # noqa: F401
