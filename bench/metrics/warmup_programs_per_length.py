"""Programs the warm-up compiles, or loads from the compile cache, per
distinct prompt length it prefills: the program's page writes compile
anew for every prompt length, so this count is what each new length a
user sends costs in compilation (the prefill buckets and the decode step
add a few programs in all)."""


def read(run):
    if not run.warmup_lengths:
        return None
    return run.warmup_programs / run.warmup_lengths
