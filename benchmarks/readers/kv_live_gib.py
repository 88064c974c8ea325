"""Live cached tokens (mean over the window's ticks) times the bytes a
token holds in the cache, from the configuration's shapes."""


def read(run, spec):  # noqa: ARG001
    w0, w1 = run["window"]
    ticks = [k for k in run.get("ticks", []) if w0 <= k["t0"] < w1]
    if not ticks:
        return None
    cell = run["cell"]
    per_token = cell.family("references").kv_bytes_per_token(cell.config)
    live = sum(k["live_tokens"] for k in ticks) / len(ticks)
    return live * per_token / 2**30
