"""95th percentile, over the requests that finished in the window, of the
time from the program's submission stamp to the dispatch of its first
prefill chunk (``Request.prefill_t - Request.submit_t``, host clock): the
wait for a slot and pages, then behind older prompts.  A program whose
requests carry no ``prefill_t`` gives nothing to read."""
from bench.harness.common import percentile


def read(run):
    reqs = [r.req for r in run.out["window"].get("finished", [])]
    q = [r.prefill_t - r.submit_t for r in reqs
         if getattr(r, "prefill_t", None) is not None]
    return 1e3 * percentile(q, 95) if q else None
