"""A number the harness itself took, by the host's clock or from JAX:
params ``which`` names it, or one of the run's named values (``res["values"]``:
``ingest_entries_per_s``, ``setup_s`` and what the cell's generators gave).
"""


def read(params: dict, ctx: dict):
    which = params["which"]
    out = ctx["out"]
    if which == "loadgen_headroom_x":
        # Entries per second the log server serves alone, over the rate
        # at which the window took them.
        return ctx["headroom"] / (ctx["entries"] / ctx["seconds"])
    if which == "ckpt_mb_per_s":
        # The table's bytes over the time from the round's last fold to
        # idle: the round's checkpoint and the cursor save, nothing else.
        bits = int(ctx["config"]["directives"]["tableBits"])
        return (1 << bits) * 32 / 1e6 / (out["t_durable"]
                                         - out["t_round_folded"])
    if which == "drain_s":
        # From the response that carries the round's last entry to the
        # checkpoint and cursor on disk (/healthz idle).
        return out["t_durable"] - out["t_last_page"]
    if which == "compile_programs":
        return float(len(ctx["compiles"].events))
    if which == "compile_seconds":
        return sum(s for _, s in ctx["compiles"].events)
    if which == "peak_hbm_gb":
        peak = ctx["device"]["memory_peak_bytes"]
        return None if peak is None else peak / 1e9
    if which == "restore_s":
        # From the call of ct_fetch.main to the first get-entries request
        # the log server answered after it: the program has its table
        # back (and its first tree head) and starts to download.
        served = [p[3] for p in out["all_pages"] if p[3] >= out["t_main_called"]]
        return min(served) - out["t_main_called"] if served else None
    if which == "table_load_pct":
        # The program's own gauge as it stood when the round was durable.
        load = out["load_at_durable"]
        return None if load is None else load * 100.0
    if which in ctx["values"]:  # the harness's two and the generators'
        return ctx["values"][which]
    raise ValueError(f"unknown harness number {which!r}")
