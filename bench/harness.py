"""What every cell shares: finding its files by name, the chip, the program
under test, its seeded weights and its tuned drivers.

A cell is an entry of ``BENCHMARK.json``'s ``workloads``.  Its
configuration is ``bench/configs/<config>.json``, its traffic mix
``bench/traffic/<traffic>.json``, and the limits of its correctness
comparison ``bench/cells/<cell>.json``.  The loop that drives it is
``bench/loops/<kind>.py``, where ``kind`` is the traffic mix's, and the
plain reference of its model is ``bench/models/<family>.py``.
"""

from __future__ import annotations

import importlib
import json
import os
import sys
from dataclasses import dataclass

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "bench")
JAX_CACHE = os.path.join(ROOT, ".jax_cache")
DRIVER_CACHE = os.path.join(ROOT, ".klaraptor_cache")
TRACE_DIR = os.path.join(ROOT, ".bench_traces")


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def _read(*parts: str) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: list
    per_layer: list

    @property
    def loop(self):
        return importlib.import_module(f"bench.loops.{self.traffic['kind']}")

    @property
    def family(self):
        return importlib.import_module(
            f"bench.models.{self.config['family']}")


def load_cell(name: str, bench_json: str | None = None) -> Cell:
    spec = _read(bench_json or os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; known: {sorted(cells)}")
    w = cells[name]

    e2e = [m for m in spec["end_to_end"]
           if name in m.get("workloads", [name])]
    # A per-layer metric without a cell list belongs to every cell that
    # reports the end-to-end metric it moves.
    moved = {m["name"] for m in e2e}
    per_layer = [m for m in spec["per_layer"]
                 if (name in m["workloads"] if "workloads" in m
                     else m["moves"] in moved)]
    return Cell(
        name=name, chips=int(w["chips"]),
        config=_read(BENCH, "configs", f"{w['config']}.json"),
        traffic=_read(BENCH, "traffic", f"{w['traffic']}.json"),
        limits=_read(BENCH, "cells", f"{name}.json")["limits"],
        end_to_end=e2e, per_layer=per_layer)


def prepare_process() -> None:
    """Caches inside the checkout, at fixed paths, whatever the environment
    names, so that two checkouts share nothing; the program's sources on
    the path.  Before JAX is imported."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = JAX_CACHE
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    os.environ["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "0"
    os.environ["KLARAPTOR_CACHE_DIR"] = DRIVER_CACHE
    src = os.path.join(ROOT, "src")
    if src not in sys.path:
        sys.path.insert(0, src)
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)


def require_chip(chips: int) -> list:
    """The accelerator devices, or ``NoChip``.  There is no CPU fallback."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < chips:
        raise NoChip(
            f"this cell needs {chips} TPU chip(s); JAX found "
            f"{len(devices)} {devices[0].platform} device(s) "
            f"({devices[0].device_kind})")
    return devices[:chips]


def device_record(devices) -> dict:
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}


def program_config(config: dict, family, base=None):
    """The program's ``ModelConfig`` for this configuration: the
    repository's own entry with the configuration's switches, norm epsilon
    and output-head width applied, checked against every size the
    configuration file states."""
    import jax.numpy as jnp

    from repro.configs import get_config

    fields = family.program_fields(config)
    pcfg = (base or get_config(config["program_id"])).replace(
        use_pallas=bool(config["use_pallas"]),
        rms_eps=config["rms_norm_eps"], dtype=jnp.dtype(config["dtype"]),
        vocab_size=fields["vocab_size"])
    wrong = {k: (getattr(pcfg, k), v) for k, v in fields.items()
             if getattr(pcfg, k) != v}
    if wrong:
        raise ValueError(
            f"the program's {config['program_id']} differs from "
            f"{config['name']}.json (program, file): {wrong}")
    return pcfg


def seeded_key(seed: int, stream: str):
    """A JAX key for any non-negative seed, one stream per purpose."""
    import jax

    from bench.generator import rng_for

    return jax.random.PRNGKey(int(rng_for(seed, stream).integers(2 ** 31)))


def make_weights(config: dict, family, pcfg, seed: int):
    """The model's weights, drawn on the device in one jitted call from
    the seed, in the dtypes the program serves them in."""
    import jax

    from repro.models import Model

    abstract = Model(pcfg).abstract_params()
    init = jax.jit(lambda k: family.init_weights(config, abstract, k))
    return jax.block_until_ready(init(seeded_key(seed, "weights")))


def tuned_kernel_specs(pcfg) -> list:
    """The kernel specs whose launch parameters the tuner chooses in this
    model's full-sequence forward."""
    from repro.core import flash_attention_spec, ssd_scan_spec

    specs = []
    if pcfg.has_block("attn"):
        specs.append(flash_attention_spec(head_dim=pcfg.head_dim,
                                          causal=pcfg.causal))
    if pcfg.has_block("mamba"):
        specs.append(ssd_scan_spec(d_head=pcfg.mamba_head_dim,
                                   d_state=pcfg.ssm_state))
    return specs


def build_drivers(pcfg, device_kind: str, log) -> None:
    """Drivers for the model's tuned kernels, built by the repository's
    oracle for this chip's parameters, or loaded from the checkout's
    driver cache where an earlier run built them."""
    from repro.core import Klaraptor, V5eSimulator
    from repro.core.device_model import hardware_for

    hw = hardware_for(device_kind)
    tuner = Klaraptor(V5eSimulator(hw), hw=hw)
    for spec in tuned_kernel_specs(pcfg):
        build = tuner.build_driver(spec)
        log(f"[drivers] {spec.name}: "
            f"{'loaded from cache' if build.from_cache else 'built'} in "
            f"{build.build_wall_seconds:.2f} s for {hw.name}")


_LOWERINGS = [0]


def lowerings() -> int:
    """Programs JAX has lowered in this process so far (a compile, or a
    load from the persistent cache, follows each): the count taken
    around the window shows whether anything compiled inside it."""
    from jax import monitoring
    from jax._src.dispatch import JAXPR_TO_MLIR_MODULE_EVENT

    if not getattr(lowerings, "registered", False):
        def count(event, _secs, **_kw):
            if event == JAXPR_TO_MLIR_MODULE_EVENT:
                _LOWERINGS[0] += 1
        monitoring.register_event_duration_secs_listener(count)
        lowerings.registered = True
    return _LOWERINGS[0]


class Choices:
    """Records the launch decisions made while it is active."""

    def __init__(self):
        self.events = []

    def __enter__(self):
        from repro.core.driver import get_choice_listener, set_choice_listener

        self._prev = get_choice_listener()
        set_choice_listener(self.events.append)
        return self

    def __exit__(self, *exc):
        from repro.core.driver import set_choice_listener

        set_choice_listener(self._prev)

    def lines(self) -> list[str]:
        return [f"[launch] {e.kernel} at {dict(e.D)}: config "
                f"{dict(e.config)} (source: {e.source})" for e in self.events]
