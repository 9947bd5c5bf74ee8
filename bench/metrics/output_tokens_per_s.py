"""Output tokens emitted inside the window over its length."""


def read(run):
    return run["window"]["output_tokens_per_s"]
