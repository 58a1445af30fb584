"""The model level's share of the chip's peak over the traced window: the
model-level triples bought in it (from the executed bits) x positions x 2 x
the trunk's active parameters counted from its published widths
(``bench/counts``), over the window times the peak."""

from bench import common, counts


def read(run):
    r = run.reduced
    if r is None or not run.model_triples:
        return None
    flops = counts.backbone_flops(run.model_triples, run.cfg["backbone_tokens"],
                                  run.bundle["active_params"])
    return 100.0 * flops / (r.window_s * common.peak_of(run.device_kind)["flops"])
