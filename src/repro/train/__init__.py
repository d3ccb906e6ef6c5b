from repro.train.engine import Engine, checkpoint_hook, log_hook  # noqa: F401
from repro.train.loop import make_grad_fn  # noqa: F401
