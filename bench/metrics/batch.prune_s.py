"""Mean seconds of one batched prune: `stats["batched"]["seconds"]` of
`prune_batch` (from engine init to the final sync), over the window's
batches."""


def read(record):
    secs = {}
    for p in record["pumps"]:
        secs.update(p["batch_seconds"])
    return sum(secs.values()) / len(secs) if secs else None
