def read(run):
    """Prompt tokens whose chunk completed plus tokens emitted between the
    window's two clock readings, over the time between them."""
    lo, hi = run["window"]
    return (run["prompt_tokens_in_window"] + run["emitted_tokens_in_window"]) / (hi - lo)
