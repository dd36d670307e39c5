"""Mean seconds per batch spent outside the batched prune: the benchmark's
span around each `pump()` that launched batches, minus those batches'
`batch.prune_s` (partitioning, upload, program wrappers, readback)."""


def read(record):
    outside = [p["seconds"] - sum(p["batch_seconds"].values())
               for p in record["pumps"] if p["batch_seconds"]]
    n = sum(len(p["batch_seconds"]) for p in record["pumps"])
    return sum(outside) / n if n else None
