"""Composable sampler-transform API for the delayed-gradient sampler zoo.

Optax-style ``(init, update)`` primitives — :func:`delay_read`,
:func:`gradients`, :func:`svrg_gradients`, :func:`stale_correction`,
:func:`langevin_noise`, :func:`apply_sgld_update`, :func:`sghmc_update`,
:func:`fused_update`, :func:`pipeline_overlap` — a :func:`chain`
combinator, :class:`DelayPolicy` implementations, and the :func:`sgld` /
:func:`svrg` / :func:`sghmc` presets reproducing the paper's four read
models across the zoo.  The unified training driver over these samplers is
:class:`repro.train.engine.Engine`; the equation-to-transform map lives in
``docs/THEORY.md`` and the transform catalog in ``docs/SAMPLERS.md``.
"""

from repro.samplers.base import Sampler, SamplerState  # noqa: F401
from repro.samplers.policies import (  # noqa: F401
    ConstantDelay,
    DelayPolicy,
    PerCoordinateDelay,
    TraceDelay,
)
from repro.samplers.presets import (  # noqa: F401
    MODES,
    sghmc,
    sgld,
    svrg,
)
from repro.samplers.transform import (  # noqa: F401
    SamplerTransform,
    StepContext,
    chain,
    stateless,
)
from repro.samplers.transforms import (  # noqa: F401
    MaskedBatch,
    SVRGState,
    apply_sgld_update,
    batch_mask,
    batch_scaled_gamma,
    delay_read,
    fused_update,
    gradients,
    langevin_noise,
    masked_gradients,
    masked_mean,
    noise_like,
    pipeline_overlap,
    sghmc_update,
    sgld_apply,
    stale_correction,
    svrg_gradients,
)
