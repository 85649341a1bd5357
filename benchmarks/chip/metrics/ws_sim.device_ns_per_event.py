"""Device nanoseconds of the work-stealing kernel per simulated event."""
import re

from readings import kernel_ns_per_event

#: The kernel's operation as the device trace names it: the Mosaic custom
#: call that ``kernels/ws_sim.py``'s ``pallas_call`` lowers to, the only
#: one on the path (``%tpu_custom_call.1 = (...) custom-call(...)``).
KERNEL = re.compile(r"^%tpu_custom_call")


def read(run):
    return kernel_ns_per_event(run, KERNEL)
