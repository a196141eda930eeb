"""Device hierarchy over the process's accelerators.

This module never touches jax device state at import time: the devices
are read when ``make_device_hierarchy`` is called.
"""
from __future__ import annotations

import jax


def make_device_hierarchy(global_window=None, capacity: int = 256):
    """Two-level window over the process's accelerators (DESIGN.md Sec. 14).

    The hierarchy follows the mesh axis convention: the *global* level
    spans devices (super-chunk claims cross the interconnect, default a
    host ``ThreadWindow`` -- on a cluster pass the KV store), while each
    node-local level is a ``DeviceWindow`` whose counter slab lives in
    that device's own memory -- within a device, claims are the
    persistent kernel's atomic counter.  Feed the result to
    ``dls.loop(runtime="hierarchical", nodes=<n_devices>, window=...)``.
    """
    from repro.core.rma import HierarchicalWindow, ThreadWindow
    from repro.device.window import DeviceWindow

    devs = jax.devices()
    locals_ = [DeviceWindow(capacity=capacity, device=d) for d in devs]
    return HierarchicalWindow(
        len(devs),
        global_window=global_window or ThreadWindow(),
        local_windows=locals_,
    )
