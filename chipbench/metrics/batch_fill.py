"""Mean requests per dispatched batch over the batch cap (the server's
``server_batch_size`` count: requests / batches)."""


def read(run):
    stats = run.server_stats
    if not stats or not stats.get("batches"):
        return None
    return stats["mean_batch_size"] / run.traffic["server"]["max_batch"]
