"""Train an assigned architecture with fault tolerance, then price the run.

Trains reduced xlstm-350m for 60 steps with checkpointing (kill it anytime;
re-running resumes bit-identically), then converts the measured step energy
(via the telemetry power model) into a cost/carbon report — energy as a
first-class training metric.

    PYTHONPATH=src python examples/train_energy.py
"""

import time

import jax
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.configs.registry import get_config
from repro.configs.shapes import ShapeConfig
from repro.core.pricing import PricingConfig, carbon_footprint_g, energy_price_usd
from repro.data.pipeline import DataConfig, batch_iterator
from repro.models import build
from repro.training import optimizer as opt
from repro.training.train_step import init_state, make_train_step
from repro.training.trainer import Trainer, TrainerConfig

import jax.numpy as jnp

CHIP_IDLE_W, CHIP_DYN_W, MFU_GUESS = 60.0, 160.0, 0.35


def main():
    enable_compile_cache()
    cfg = get_config("xlstm-350m", reduced=True)
    api = build(cfg)
    shape = ShapeConfig("t", 128, 8, "train")
    ocfg = opt.OptimizerConfig(total_steps=60, warmup_steps=6)
    step = jax.jit(make_train_step(api, ocfg), donate_argnums=(0,))
    state = init_state(api, jax.random.PRNGKey(0), ocfg)

    trainer = Trainer(
        step, state, lambda s: batch_iterator(api, shape, DataConfig(seed=0), start_step=s),
        TrainerConfig(total_steps=60, checkpoint_every=20, checkpoint_dir="/tmp/repro_train_energy"),
        on_step=lambda i, m: print(f"step {i:3d} loss={float(m['loss']):.4f}") if i % 10 == 0 else None,
    )
    t0 = time.time()
    report = trainer.run()
    wall = time.time() - t0
    print(f"\n{report.steps_run} steps, final loss {report.final_loss:.4f}, "
          f"resumed_from={report.resumed_from}, stragglers={report.straggler_steps}")

    # Energy accounting for the run (TPU-chip power model; on this CPU host
    # the same formula with the host's power envelope applies).
    busy = sum(report.step_times)
    energy_j = CHIP_IDLE_W * wall + CHIP_DYN_W * MFU_GUESS * busy
    usd = float(energy_price_usd(jnp.asarray(energy_j)))
    co2 = float(carbon_footprint_g(jnp.asarray(energy_j)))
    print(f"run energy ~{energy_j:.0f} J  ->  ${usd:.6f}  /  {co2:.3f} gCO2 "
          f"({energy_j / max(report.steps_run, 1):.1f} J/step)")


if __name__ == "__main__":
    main()
