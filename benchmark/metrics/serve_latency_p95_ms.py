"""95th percentile of the window's `serve.request` spans, ms: the tail a
caller of `ccs serve` waits for (the end-to-end `zmws_per_s` of a closed
loop guards the mean: mean latency = sessions / zmws_per_s)."""

from harness import manifest


def read(inp):
    return manifest.load_by_path("metrics", "serve_latency_p50_ms").quantile(inp, 0.95)
