"""Core MCIM library: multi-cycle folded integer multipliers in JAX.

Most callers should start one level up, at :mod:`repro.designs`: a
declarative ``DesignSpec`` compiled by ``generate()`` wires planner,
timing model, bank and sharding together.  The layers below stay public
for direct use:

  limbs            -- limb representation + PPM / compressor / final adders
  mcim_mul         -- configurable folded multiply (fb/ff/karatsuba/star)
  MCIMConfig       -- generator parameters (arch, ct, levels, adder, signed)
  make_multiplier  -- jitted fixed-width multiplier factory
  mul32x32_64      -- 32x32->64 multiply on uint32 lanes (for RNG / exact)
  planner          -- design-point selection (paper Table VIII policy)
  timing_model     -- clock/latency model filtering that selection
  bank             -- executable multiplier banks for planner Plans
                      (pluggable schedulers, the core and fused backends,
                      sharded execution)
  area_model       -- ASIC-area cost model used by benchmarks/
  power_model      -- switching-energy / peak-power cost model
                      (the paper's 33%-energy / 65%-peak-power claims)
"""
from . import limbs
from . import area_model
from . import power_model
from . import planner
from . import bank
from .bank import Bank, BankReport, sharded_execute
from .mcim import MCIMConfig, mcim_mul, make_multiplier, mul32x32_64
from .schoolbook import star_mul, feedback_mul, feedforward_mul
from .karatsuba import karatsuba_mul, karatsuba_ppm

__all__ = [
    "limbs", "area_model", "power_model", "planner", "bank",
    "Bank", "BankReport", "sharded_execute",
    "MCIMConfig", "mcim_mul", "make_multiplier", "mul32x32_64",
    "star_mul", "feedback_mul", "feedforward_mul",
    "karatsuba_mul", "karatsuba_ppm",
]
