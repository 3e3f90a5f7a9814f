"""Factories for the builtin targets.

Every factory returns a plain :class:`~repro.core.targets.Target` value;
keyword overrides pass straight through, so a caller can re-declare any
pricing field without subclassing anything:

    reg = default_registry()
    reg.register(xla_cpu(name="xla-cpu-lowlat", dispatch_s=5e-6), replace=False)

The three host backends exist on every machine:

* ``numpy-eager``     — eager host execution, never jitted; serves the
  ``"numpy"`` dialect of an op's variant table, whose payloads run on
  the host.  An op without a NumPy variant falls back to the reference
  ``fn``, which runs eagerly wherever its inputs live.  Models the
  paper's plain-CPU lane: minimal dispatch, no device handoff cost on
  its own side.
* ``xla-cpu``         — the reference payloads under ``jax.jit``, pinned
  to the host CPU (``jax.devices("cpu")[0]``) even where a TPU is the
  default backend (the compiled path's bitwise-gated fast lane).
* ``pallas-interpret``— serves the ``"pallas"`` dialect with the Pallas
  kernels in interpret mode on the host CPU, tolerance-gated against the
  reference oracle per the blockwise-accumulation buckets in
  ``targets.VARIANT_TOL``.

``discover_devices()`` adds one jitted ``ref``-dialect target per real
``jax.devices()`` entry (``cpu:0``, ``tpu:0``, ...), device-pinned via
``Target.device``; non-CPU platforms are priced as accelerators.  A
device target that should serve the compiled kernels is
``device_target(dev, dialect="pallas", interpret=False)``.
"""
from __future__ import annotations

from typing import Any

import jax

from ..targets import Target, TargetRegistry


def _host_cpu():
    return jax.devices("cpu")[0]


def numpy_eager(**overrides: Any) -> Target:
    kw: dict[str, Any] = dict(
        name="numpy-eager", kind="host", dialect="numpy", jit=False,
        is_accelerator=False, dispatch_s=3e-6, handoff_s=0.0,
        power_compute=15.0, power_memory=11.0)
    kw.update(overrides)
    return Target(**kw)


def xla_cpu(**overrides: Any) -> Target:
    # atol/rtol declare the jit-probe tolerance: XLA fusion reorders f32
    # accumulation, so eager-vs-jit is rarely bitwise for softmax/einsum
    # compositions — without a declared tolerance the probe would reject
    # the jit and serve the ~100x slower eager composition, which is not
    # what "the jitted reference lane" means.  handoff_s is deliberately
    # conservative (1 ms): leaving a fused XLA segment forfeits fusion
    # that the per-op cost cells cannot see, so a lane switch must earn
    # a wide measured margin before the planner takes it.
    kw: dict[str, Any] = dict(
        name="xla-cpu", kind="cpu", dialect="ref", jit=True,
        device=_host_cpu(), is_accelerator=True, dispatch_s=2e-5, handoff_s=1e-3,
        power_compute=17.0, power_memory=12.0, atol=1e-5, rtol=1e-5)
    kw.update(overrides)
    return Target(**kw)


def pallas_interpret(**overrides: Any) -> Target:
    kw: dict[str, Any] = dict(
        name="pallas-interpret", kind="interpret", dialect="pallas",
        jit=True, interpret=True, device=_host_cpu(),
        is_accelerator=True, dispatch_s=5e-5,
        handoff_s=1e-3, power_compute=20.0, power_memory=12.0)
    kw.update(overrides)
    return Target(**kw)


def device_target(dev: Any, **overrides: Any) -> Target:
    """A jitted target pinned to one JAX device, serving the reference
    dialect unless ``dialect`` says otherwise.  The reference dialect
    declares a 1e-5 tolerance for its jit (XLA fusion reorders float
    accumulation); a kernel dialect such as ``"pallas"`` declares none,
    so its variants are gated at the ``VARIANT_TOL`` bucket of each
    output's dtype."""
    platform = getattr(dev, "platform", "cpu")
    kw: dict[str, Any] = dict(
        name=f"{platform}:{getattr(dev, 'id', 0)}", kind=platform,
        dialect="ref", jit=True, device=dev,
        is_accelerator=platform != "cpu",
        dispatch_s=2e-5, handoff_s=1e-3 if platform != "cpu" else 5e-4,
        meta={"device_kind": getattr(dev, "device_kind", platform)})
    kw.update(overrides)
    if kw["dialect"] == "ref":
        kw.setdefault("atol", 1e-5)
        kw.setdefault("rtol", 1e-5)
    return Target(**kw)


def discover_devices() -> list[Target]:
    """One target per ``jax.devices()`` entry of the default backend.  A
    backend that fails to initialise raises here: its lanes must not
    silently vanish from the registry."""
    return [device_target(d) for d in jax.devices()]


def default_registry(*, devices: bool = True) -> TargetRegistry:
    """The builtin target set: `numpy-eager` + `xla-cpu` +
    `pallas-interpret`, plus (``devices=True``) every real JAX device."""
    reg = TargetRegistry([numpy_eager(), xla_cpu(), pallas_interpret()])
    if devices:
        for t in discover_devices():
            if t.name not in reg:
                reg.register(t)
    return reg
