"""The experts' grouped matmuls against their roofline: the least time the
chip needs for the work they require (``bench/counts_moe.expert_work``: the
trunk's merged lanes x positions, each routed to top-k experts, in every
expert layer of every trunk run in the window), over the grouped-matmul
instructions' summed device time from the trace."""

from bench import common, counts, counts_moe, scopes, trunk_scopes


def read(run):
    shape = run.bundle.get("moe_shape")
    sc = scopes.from_run(run)
    if shape is None or sc is None:
        return None
    t = trunk_scopes.grouped_matmul_s(sc)
    if t <= 0:
        return None
    runs = sc.branch_runs("trunk")
    cfg = run.bundle["session"].config
    lanes = cfg.merged_capacity or run.bundle["session"].max_tenants * cfg.plan_size
    flops, nbytes = counts_moe.expert_work(
        lanes * run.cfg["backbone_tokens"], shape["top_k"], shape["experts"],
        shape["d_model"], shape["d_ff_expert"], shape["weight_bytes"], shape["weight_bytes"])
    t_min, _ = counts.roofline_seconds(flops, nbytes, common.peak_of(run.device_kind))
    return 100.0 * t_min * shape["layers"] * runs / t
