"""repro — Stochastic Gradient Langevin with Delayed Gradients (async-SGLD).

A production-grade JAX framework reproducing Kungurtsev, Chatterjee, Alistarh
(2020): delayed-gradient SGLD (Sync / W-Con / W-Icon) as a first-class
distributed sampler, plus the substrate (model zoo, data pipeline,
checkpointing, launcher, sharding) needed to run it at scale.
"""

__version__ = "0.1.0"
