"""Share of the kernel tuner's lookups that found a tuned configuration
(`apex_tpu.tune.stats()`: hits / (hits + misses), in percent) while the
programs of this run were traced; the rest ran on heuristics."""


def compute(observed):
    from apex_tpu import tune

    stats = tune.stats()
    lookups = stats["hits"] + stats["misses"]
    return 100.0 * stats["hits"] / lookups if lookups else None
