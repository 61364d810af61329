"""95th percentile, over the requests that finished in the window, of the
time from the dispatch of its first prefill chunk to its first token on
the host (``Request.first_token_t - Request.prefill_t``, host clock): its
own chunks.  A program whose requests carry no ``prefill_t`` gives
nothing to read."""
from bench.harness.common import percentile


def read(run):
    reqs = [r.req for r in run.out["window"].get("finished", [])]
    p = [r.first_token_t - r.prefill_t for r in reqs
         if getattr(r, "prefill_t", None) is not None]
    return 1e3 * percentile(p, 95) if p else None
