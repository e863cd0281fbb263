"""Seconds the program's launches spent compiling, from its own counter
(``repro.tracing.compile_counters``): JAX's trace, lowering and
backend-compile events recorded inside ``s2v.count_keys_chunk`` spans.  In
a run that is the warm launch, since the window's launches compile nothing.

A persistent-cache load happens inside the backend-compile event; its
retrieval time is a part of that event, so it is printed beside the value on
standard error and not added.  A program without the counter gives nothing.
"""

import sys

PARTS = ("trace", "lower", "backend_compile")
LAUNCH_SPAN = "count_keys_chunk"


def read(run):
    try:
        from repro.tracing import compile_counters
    except ImportError:
        return None
    got = compile_counters(LAUNCH_SPAN)
    if not any(got[p]["count"] for p in PARTS):
        return None
    value = sum(got[p]["seconds"] for p in PARTS)
    parts = " ".join(f"{p}_s={got[p]['seconds']:.3f}" for p in PARTS)
    print(f"[bench] compile_s={value:.3f} ({parts}) cache_retrieval_s={got['cache_retrieval']['seconds']:.3f} "
          f"cache_hits={got['cache_retrieval']['count']}", file=sys.stderr, flush=True)
    return value
