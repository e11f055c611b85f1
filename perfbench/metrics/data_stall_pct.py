"""Share of the window the training loop waited for its next batch:
``ftl_data_stall_seconds_total`` over the window."""


def read(ctx):
    window = (ctx.get("train") or {}).get("window")
    if not window or not window.get("window_s"):
        return None
    return 100.0 * window["data_stall_s"] / window["window_s"]
