"""DeviceWindow: the paper's RMA window relocated to device memory.

The window is an int32 slab living as a jax device array (HBM on an
accelerator) with the same append-only key directory as the shared-memory
slab (``repro.pt.window``): a key is published once, its slot index never
moves, counters are monotonic per loop id.

Fallback ladder (what "atomic fetch-add against device memory" means on
each rung -- ``capability_tier()`` reports which one this process gets):

  ``atomics``   GPU backends expose real device atomics to Pallas kernels;
                the persistent kernel's claim loop would use them across
                concurrent blocks.  Probed, not yet exercised (this repo's
                CI has no GPU) -- the tier exists so ``availability()``
                consumers can route on it.
  ``aliased``   compiled TPU/CPU: the slab is threaded through jitted
                updates (host side) and through ``input_output_aliases``
                (kernel side), so every RMW is an in-place accumulator
                update on the *same* device buffer -- one logical window,
                never copied per claim.
  ``interpret`` CPU CI: the identical aliased-slab protocol runs under the
                Pallas interpreter.  ``chip_smoke.py`` checks the compiled
                path on the TPU: its schedules equal the host ``plan()``
                index for index.

Host-side ``fetch_add``/``read``/``reset`` satisfy the ordinary ``Window``
contract, so every existing consumer (``OneSidedRuntime``, sessions,
``HierarchicalWindow`` composition) works unchanged -- the counters just
happen to live on the accelerator.  ``fetch_add_traced`` is the
host-callback shim: an ordered ``io_callback`` RMW usable from *traced*
code (jitted host-plane claim loops) against the very same counters.

The in-kernel protocol (``device/persistent.py``) borrows the slab with
``slab()``/``slot()`` and hands the mutated counters back via ``adopt``.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Optional, Sequence

import numpy as np

from repro.core.rma import Window


@functools.lru_cache(maxsize=2)
def _updater(donate: bool):
    """The jitted aliased-accumulator update: (old, new_slab).

    Donation makes the update genuinely in-place on backends that support
    buffer donation; the CPU backend ignores donation (with a warning), so
    the interpret tier compiles without it -- same values either way.
    """
    import jax

    def fa(slab, slot, delta):
        return slab[slot], slab.at[slot].add(delta)

    return jax.jit(fa, donate_argnums=(0,) if donate else ())


class DeviceWindow(Window):
    """Passive-target window over named int32 counters in device memory."""

    def __init__(self, capacity: int = 256, device=None):
        ok, reason = self.availability()
        if not ok:
            raise RuntimeError(f"DeviceWindow unavailable: {reason}")
        import jax
        import jax.numpy as jnp

        if capacity <= 0:
            raise ValueError("capacity must be positive")
        self.capacity = capacity
        self.tier = self.capability_tier()
        slab = jnp.zeros((capacity,), jnp.int32)
        if device is not None:
            slab = jax.device_put(slab, device)
        self.device = device
        self._slab = slab
        self._slots: Dict[str, int] = {}
        self._fa = _updater(donate=self.tier != "interpret")
        self.n_rmw = 0  # RMWs paid against this window (host + adopted)

    # -- capability probe (satellite: availability precedent) -------------
    @classmethod
    def availability(cls) -> "tuple[bool, str]":
        """Usable iff jax can place an array on some device.

        Like the kvstore/shm probes this is the single source of truth:
        ``make_window("device")`` and the test skips both route through it.
        """
        try:
            import jax

            jax.devices()
            return True, ""
        except Exception as e:
            return False, f"no jax device backend available ({e!r})"

    @classmethod
    def capability_tier(cls) -> str:
        """Which rung of the fallback ladder this process gets
        ('atomics' | 'aliased' | 'interpret'), see module docstring."""
        import jax

        backend = jax.default_backend()
        if backend == "gpu":
            return "atomics"
        if backend == "cpu":
            return "interpret"
        return "aliased"

    # -- slab plumbing for the persistent kernels -------------------------
    def slot(self, key: str) -> int:
        """The key's slab index (published on first use, never moves)."""
        idx = self._slots.get(key)
        if idx is None:
            if len(self._slots) >= self.capacity:
                raise RuntimeError(
                    f"device window directory full ({self.capacity} keys); "
                    "create the window with a larger capacity")
            idx = len(self._slots)
            self._slots[key] = idx
        return idx

    def keys(self) -> List[str]:
        return list(self._slots)

    def slab(self):
        """The live counter slab (hand this to the protocol kernel)."""
        return self._slab

    def adopt(self, slab, n_rmw: int = 0) -> None:
        """Take ownership of a kernel-mutated slab (+ its in-kernel RMWs)."""
        if slab.shape != (self.capacity,):
            raise ValueError(
                f"adopted slab shape {slab.shape} != ({self.capacity},)")
        self._slab = slab
        self.n_rmw += int(n_rmw)

    # -- Window contract (host side) --------------------------------------
    def fetch_add(self, key: str, delta: int) -> int:
        idx = self.slot(key)
        self.n_rmw += 1
        old, self._slab = self._fa(self._slab, idx, delta)
        return int(old)

    def read(self, key: str) -> int:
        return int(self._slab[self.slot(key)])

    def reset(self, key: str, value: int = 0) -> None:
        self._slab = self._slab.at[self.slot(key)].set(value)

    def read_many(self, keys: Sequence[str]) -> List[int]:
        # one device->host transfer for the whole batch
        host = np.asarray(self._slab)
        return [int(host[self.slot(k)]) for k in keys]

    # -- host-callback shim for traced callers ----------------------------
    def fetch_add_traced(self, key: str, delta):
        """Atomic fetch-add callable from *traced* host-plane code.

        An ordered ``io_callback`` so RMWs from inside ``jit`` serialize
        against each other and against host-side ``fetch_add`` calls --
        the shim that lets interpret-mode CI drive the one window from
        both planes byte-exactly.  Returns a traced int32 (the old value).
        """
        import jax
        import jax.numpy as jnp
        from jax.experimental import io_callback

        self.slot(key)  # publish outside the trace

        def _host_rmw(d):
            return np.int32(self.fetch_add(key, int(d)))

        return io_callback(_host_rmw, jax.ShapeDtypeStruct((), jnp.int32),
                           jnp.asarray(delta, jnp.int32), ordered=True)

    def __repr__(self) -> str:  # pragma: no cover
        return (f"DeviceWindow(capacity={self.capacity}, tier={self.tier!r}, "
                f"keys={len(self._slots)})")
