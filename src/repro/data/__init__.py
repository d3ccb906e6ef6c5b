from repro.data.pipeline import Prefetcher, ar1_stream  # noqa: F401
from repro.data.synthetic import make_batch, token_stream  # noqa: F401
