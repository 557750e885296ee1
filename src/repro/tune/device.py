"""Device capability table — the hardware half of "hardware-aware".

The paper's runtime adapts tile tasking to each architecture (Fugaku's
512-bit SVE, A100's tensor cores, Frontier's MI250X); our SPMD analogue is a
static ``DeviceSpec`` per accelerator kind holding exactly the quantities the
cost model and plan validator need:

* MXU/matmul native shape and block alignment,
* on-chip fast-memory budget (VMEM on TPU, SMEM+L1 on GPU),
* HBM bandwidth,
* peak LOW-precision matmul throughput,
* a per-kernel-task overhead (large in CPU interpret mode, where each grid
  step executes as Python — the model must know this to prefer XLA paths).

Per-format MXU pass costs are *not* stored here: each registered
:class:`~repro.core.formats.PrecisionFormat` carries its own per-device
``pass_cost`` table (fp32 = 3 bf16 MXU passes on TPU, 2 tensor-core passes
on A100, …) and ``DeviceSpec.format_cost`` resolves it for this device —
registering a new format never requires touching the device table.

Specs for hardware this container does not have are retained so plan caches
can be built *for* a target architecture on any host (cache-only CI mode).
"""
from __future__ import annotations

import dataclasses
from typing import Mapping

import jax

from repro.core.formats import DEFAULT_FORMATS, FormatSet, get_format


@dataclasses.dataclass(frozen=True)
class DeviceSpec:
    """Capabilities of one accelerator kind, as seen by the tuner."""

    kind: str                       # canonical name, also the cache key part
    mxu: tuple[int, int]            # native matmul unit shape
    alignment: int                  # required block-dim multiple on real hw
    vmem_bytes: int                 # fast on-chip memory for kernel blocks
    smem_bytes: int                 # scalar memory (prefetch maps live here)
    hbm_gbps: float                 # HBM bandwidth, GB/s
    low_tflops: float               # peak LOW-class (bf16) matmul TFLOP/s
    task_overhead_s: float          # fixed cost per kernel grid step
    interpret: bool                 # Pallas runs in interpret mode here
    #: dtype names this device's Pallas compiler cannot load, compute or
    #: slice in; plans whose format set touches one stay on the XLA paths
    pallas_bad_dtypes: tuple[str, ...] = ()

    def format_cost(self, name: str) -> float:
        """Relative MXU passes of a tile task at format ``name`` here."""
        return get_format(name).cost_on(self.kind)

    @property
    def class_cost(self) -> Mapping[int, float]:
        """DEPRECATED — default-set class code -> pass cost (registry view)."""
        return {c: self.format_cost(DEFAULT_FORMATS.names[c])
                for c in DEFAULT_FORMATS.codes}

    def class_weight(self, frac_high: float, frac_low8: float = 0.0,
                     fset: FormatSet = DEFAULT_FORMATS) -> float:
        """Mean MXU passes per tile task given role fractions."""
        frac_low = 1.0 - frac_high - frac_low8
        w = (self.format_cost(fset.names[fset.high]) * frac_high
             + self.format_cost(fset.names[fset.low]) * frac_low)
        if fset.low8 is not None:
            w += self.format_cost(fset.names[fset.low8]) * frac_low8
        return w


def _tpu(kind, vmem_mb, gbps, tflops, overhead=2e-6,
         pallas_bad_dtypes=()) -> DeviceSpec:
    return DeviceSpec(
        kind=kind, mxu=(128, 128), alignment=128,
        vmem_bytes=vmem_mb * 2**20, smem_bytes=64 * 2**10,
        hbm_gbps=gbps, low_tflops=tflops,
        task_overhead_s=overhead, interpret=False,
        pallas_bad_dtypes=tuple(pallas_bad_dtypes))


#: Known accelerators.  Numbers are public peak specs (bf16 / HBM); they feed
#: a *relative* roofline model, so being a few percent off is harmless.
#: Per-format pass asymmetries (fp32 = 3 passes on TPU, 2 on A100 tensor
#: cores, fp8 at double rate on A100 …) live in the format registry.
DEVICE_TABLE: dict[str, DeviceSpec] = {
    "tpu-v4": _tpu("tpu-v4", vmem_mb=16, gbps=1228.0, tflops=275.0),
    # Mosaic for v5e refuses float16 loads ("Invalid vector type for
    # load") and bf16->f16 casts (tests/test_tpu_compile.py)
    "tpu-v5e": _tpu("tpu-v5e", vmem_mb=16, gbps=819.0, tflops=197.0,
                    pallas_bad_dtypes=("float16",)),
    "tpu-v5p": _tpu("tpu-v5p", vmem_mb=16, gbps=2765.0, tflops=459.0),
    "tpu-v6e": _tpu("tpu-v6e", vmem_mb=32, gbps=1640.0, tflops=918.0),
    "gpu-a100": DeviceSpec(
        kind="gpu-a100", mxu=(16, 16), alignment=8,
        vmem_bytes=192 * 2**10, smem_bytes=64 * 2**10,
        hbm_gbps=2039.0, low_tflops=312.0,
        task_overhead_s=2e-6, interpret=False),
    "gpu-mi250x": DeviceSpec(
        kind="gpu-mi250x", mxu=(16, 16), alignment=8,
        vmem_bytes=160 * 2**10, smem_bytes=64 * 2**10,
        hbm_gbps=1638.0, low_tflops=191.5,
        task_overhead_s=2e-6, interpret=False),
    # CPU / interpret fallback: Pallas kernels execute per-grid-step in
    # Python, so task overhead dominates everything; XLA dot paths run at
    # a few hundred GFLOP/s.  The VMEM budget mirrors v5e so plans stay
    # portable to the real target.
    "cpu-interpret": DeviceSpec(
        kind="cpu-interpret", mxu=(1, 1), alignment=1,
        vmem_bytes=16 * 2**20, smem_bytes=64 * 2**10,
        hbm_gbps=30.0, low_tflops=0.2,
        task_overhead_s=2e-3, interpret=True),
}


def device_table() -> dict[str, DeviceSpec]:
    return dict(DEVICE_TABLE)


#: substrings of ``jax.Device.device_kind`` -> table key
_KIND_PATTERNS = (
    ("v6e", "tpu-v6e"), ("v6 lite", "tpu-v6e"),
    ("v5p", "tpu-v5p"),
    ("v5e", "tpu-v5e"), ("v5 lite", "tpu-v5e"),
    ("v4", "tpu-v4"),
    ("a100", "gpu-a100"), ("h100", "gpu-a100"),
    ("mi250", "gpu-mi250x"), ("mi300", "gpu-mi250x"),
)


def detect_device(device: "jax.Device | None" = None) -> DeviceSpec:
    """Map the running accelerator to a DeviceSpec.

    ``repro.configure(device=…)`` (or the ``REPRO_TUNE_DEVICE`` env var it
    wraps) overrides detection with a table key — this is how a CPU host
    builds (or validates) a plan cache for a TPU target.
    """
    from repro import config
    forced = config.get("device")
    if forced:
        if forced not in DEVICE_TABLE:
            raise KeyError(
                f"REPRO_TUNE_DEVICE={forced!r} not in device table "
                f"{sorted(DEVICE_TABLE)}")
        return DEVICE_TABLE[forced]
    if device is None:
        device = jax.devices()[0]
    kind = device.device_kind.lower()
    if device.platform in ("tpu", "gpu"):
        for pat, key in _KIND_PATTERNS:
            if pat in kind:
                return DEVICE_TABLE[key]
        # no assumed peaks for hardware the table does not describe
        raise KeyError(
            f"{device.platform} device_kind {device.device_kind!r} is not "
            f"in the device table {sorted(DEVICE_TABLE)}; add a DeviceSpec "
            "or force one with repro.configure(device=...)")
    return DEVICE_TABLE["cpu-interpret"]
