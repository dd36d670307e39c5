"""Median queue wait of the window's answered queries: `QueryResult.wait_s`
of the serving engine, from submission to the launch of the query's batch."""
import statistics


def read(record):
    waits = [r["answer"].wait_s for r in record["records"]
             if r["status"] == "ok" and r["answer"].wait_s is not None]
    return statistics.median(waits) if waits else None
