"""Prompt tokens the prefix trie let admission skip over all prompt tokens
admitted in the traced sub-window (the engine's prefix_stats() counters),
in percent."""
from bench.readings import counter_delta


def read(ctx):
    total = counter_delta(ctx, "prefill_tokens")
    if not total:
        return None
    return 100.0 * counter_delta(ctx, "prefill_tokens_skipped") / total
