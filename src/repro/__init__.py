"""repro: FaasMeter (Energy-First Serverless Computing) as a JAX/TPU framework.

A fleet energy controller: per-node telemetry in, per-function energy out,
with caps, prices and retraining driven from those footprints.

- ``repro.core``        -- the paper's contribution: statistical power
  disaggregation, Kalman-filtered online estimation, Shapley fair attribution,
  power capping, pricing, and the energy metrology framework (validation
  metrics + marginal-energy ground truth); ``core.engine`` holds the jitted
  fleet stages, ``core.sessions`` the live streaming sessions.
- ``repro.serving``     -- the control plane (``profile_fleet``, the control
  loop, the slot scheduler).
- ``repro.telemetry``   -- power-source substrate (IPMI/plug/RAPL-like
  simulated sensors with matched noise/lag/quantization pathologies).
- ``repro.workload``    -- Azure-trace-style FaaS workload generation.
- ``repro.distributed`` -- ``FleetMesh`` (the fleet's node-axis sharding) and
  the model zoo's logical-axis rules.
- ``repro.kernels``     -- the Pallas ``disagg_gram`` kernel + jnp references.
- ``repro.tracing``     -- the ``faasmeter.*`` host spans of the tick path.

The serving demo (``examples/serve_energy.py``) alone uses ``repro.models``
(reduced LM architectures), ``repro.configs``, ``serving.engine``,
``serving.kv_cache`` and the attention/rmsnorm kernels;
``scripts/check_layering.py`` fences them off from everything above.
"""

from repro.version import __version__

__all__ = ["__version__"]
