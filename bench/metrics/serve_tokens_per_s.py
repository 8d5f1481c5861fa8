"""Prompt tokens prefilled plus output tokens emitted in the window,
divided by the window."""


def read(record):
    n = record["prompt_tokens"] + record["output_tokens"]
    return n / record["window_s"] if n else None
