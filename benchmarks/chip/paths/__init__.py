"""Window runners, one per path the benchmark drives (``config["path"]``)."""
