"""Auto-registered kernels: introspected specs wired into the runtime.

``auto_register`` is the one-call path from "I wrote a Pallas kernel" to
"the whole KLARAPTOR pipeline serves it": it introspects the kernel into a
:class:`KernelSpec` (derive.py) and records an :class:`AutoKernel` in a
process-wide table.  The AutoKernel is the glue every other layer uses:

  * ``choose(D)``        -- launch parameters through the standard
    ``choose_or_default`` dispatch (override > driver > default),
    i.e. the same path ``kernels/ops.py`` uses for hand-specced kernels;
  * ``ensure_driver``    -- registry / artifact-cache read-through, then a
    full collect -> fit -> codegen build against a device oracle if nothing
    is cached.  Because the introspected spec's ``source_fingerprint`` is
    part of the cache key, editing the kernel body makes every stale
    artifact unreachable by construction;
  * ``fit_config``       -- snap a chosen config onto an actual shape with
    the *derived* granularities (parsed back out of the synthesized
    ``"p % g == 0"`` constraints), replacing the hand-maintained alignment
    constants of the hand-specced ops.
"""

from __future__ import annotations

import re
from dataclasses import dataclass, field
from typing import Mapping

from repro.core.device_model import DeviceModel, HardwareParams, V5E
from repro.core.driver import (DriverProgram, choose_or_default, fit_tile,
                               get_driver)
from repro.core.kernel_spec import KernelSpec

from .derive import spec_from_kernel
from .gridspec import GridSpec

__all__ = ["AutoKernel", "auto_register", "get_auto", "auto_kernels"]

Dims = Mapping[str, int]

_ALIGN_RE = re.compile(r"^\s*(\w+)\s*%\s*(\d+)\s*==\s*0\s*$")


@dataclass
class AutoKernel:
    """One introspected kernel, ready for driver dispatch and tuning."""

    spec: KernelSpec
    grid_spec: GridSpec
    fn: object
    hw: HardwareParams = V5E
    _alignments: dict[str, int] | None = field(default=None, repr=False)

    @property
    def name(self) -> str:
        return self.spec.name

    @property
    def defaults(self) -> dict[str, int]:
        """Static fallback config: the GridSpec's, else mid-grid candidates."""
        if self.grid_spec.defaults:
            return dict(self.grid_spec.defaults)
        out = {}
        for p in self.spec.program_params:
            cand = self.spec.default_candidates(p, {})
            out[p] = int(cand[len(cand) // 2])
        return out

    def alignments(self) -> dict[str, int]:
        """Derived per-parameter granularity, parsed once from the
        synthesized ``"p % g == 0"`` constraint strings (the constraint set
        is frozen per AutoKernel, so the parse is cached)."""
        if self._alignments is None:
            out = {p: 1 for p in self.spec.program_params}
            for c in self.spec.constraints:
                m = _ALIGN_RE.match(c)
                if m and m.group(1) in out:
                    out[m.group(1)] = max(out[m.group(1)], int(m.group(2)))
            self._alignments = out
        return dict(self._alignments)

    def fit_config(self, config: Dims, dims: Dims) -> dict[str, int]:
        """Snap a chosen config to divide the actual extents ``dims``
        (program param -> the data extent it tiles) with the shared
        ``core.driver.fit_tile`` helper, at the *derived* granularity --
        what the hand-specced ops hard-code per parameter."""
        align = self.alignments()
        out = dict(config)
        for a in self.spec.grid:
            p = a.block
            if p is None or not isinstance(a.data, str) or a.data not in dims:
                continue
            out[p] = fit_tile(int(dims[a.data]), int(out[p]),
                              align.get(p, 1))
        return out

    def choose(self, D: Dims, hw: HardwareParams | None = None
               ) -> dict[str, int]:
        """Launch parameters via the standard dispatch chain."""
        return choose_or_default(self.name, D, self.defaults,
                                 hw=hw or self.hw)

    def ensure_driver(self, device: DeviceModel | None = None,
                      **build_kwargs) -> DriverProgram:
        """Registered/cached driver, or build one against ``device``.

        The build writes through the artifact cache under a key that
        includes the kernel's source fingerprint, so the fleet shares it
        and a changed kernel body never reuses it.
        """
        drv = get_driver(self.name, hw=self.hw)
        if drv is not None:
            return drv
        from repro.core.device_model import V5eSimulator
        from repro.core.tuner import Klaraptor

        kl = Klaraptor(device or V5eSimulator(self.hw), hw=self.hw)
        return kl.build_driver(self.spec, **build_kwargs).driver


_AUTO: dict[str, AutoKernel] = {}


def auto_register(fn, grid_spec: GridSpec, *,
                  hw: HardwareParams = V5E) -> AutoKernel:
    """Introspect ``fn`` and register it for tuned dispatch.

    Idempotent per spec name, kernel body, *and* tuning policy:
    re-registering an identical kernel returns the existing AutoKernel;
    re-registering with a changed kernel body or a changed GridSpec policy
    (candidates, hints, defaults) under the same name replaces it (a new
    source fingerprint additionally routes cache lookups to fresh
    artifacts).
    """
    spec = spec_from_kernel(fn, grid_spec, hw=hw)
    existing = _AUTO.get(spec.name)
    if existing is not None and existing.spec == spec and \
            existing.hw.name == hw.name and \
            existing.grid_spec.defaults == grid_spec.defaults:
        return existing
    ak = AutoKernel(spec=spec, grid_spec=grid_spec, fn=fn, hw=hw)
    _AUTO[spec.name] = ak
    return ak


def get_auto(name: str) -> AutoKernel | None:
    return _AUTO.get(name)


def auto_kernels() -> dict[str, AutoKernel]:
    """Snapshot of every auto-registered kernel in this process."""
    return dict(_AUTO)
