"""Model step: the least model FLOPs of every prompt token prefilled and
every token decoded in the window, over the window's length times the
chip's bf16 peak, in percent.  ``serve_mfu`` moves ``tok_s`` (offline
batch); ``serve_mfu.code`` moves ``ttft_p90_ms`` (code completion)."""

from bench.readers import mfu_percent


def read(run):
    return mfu_percent(run)
