"""The deployment ``gpu-preempt-10k``: a GPU fleet kept full of low-priority
batch work on which high-priority services place only by evicting
(BASELINE.json config 4). Its five parts, named in
``configs/gpu-preempt-10k.json``: ``fleet`` (nodes with a device group, the
scheduler's configuration), ``jobs`` (priority, a device ask,
``distinct_hosts``), ``warm`` (the fill, then the window's own cycle until
every shape is warm), ``driver`` (occupancy read from the store: an
eviction moves it without a request) and ``judge`` (the guarantees of
eviction, and the choice of victims and nodes against
``reference/preemption.py``).
"""
