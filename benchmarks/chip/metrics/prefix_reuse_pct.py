"""Prompt tokens the prefix pool supplied in the window over the prompt
tokens of the requests counted."""

from . import _engine


def read(record):
    reused = _engine.kv_delta(record, "tokens_reused")
    sent = record.get("prompt_tokens_counted")
    if reused is None or not sent:
        return None
    return 100.0 * reused / sent
