"""build_s: host seconds of the program's build call in set-up
(``repro.index.build``; ``TunedTier.__init__`` -> ``ShardedIndex.build``).
Moves setup_s."""


def read(ctx):
    return ctx["timings"].get("build_s")
