"""General generators, one per driver kind. A cell's traffic is the
``traffic`` object of its ``workloads/<cell>.json``: parameters only."""
