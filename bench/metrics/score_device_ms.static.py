"""``score_device_ms.stream``'s reader, under a name of its own in the cells without a
stream, so that each family of cells moves and is bounded by its own
end-to-end metrics."""

from bench.common import load_module

read = load_module("metrics", "score_device_ms.stream").read
