"""The deployment ``rackloss-10k``: one rack of c2m-10k's fleet after another
goes dark under 50,000 live allocations (Nomad's heartbeat expiry: the
rack's nodes marked down, one node eval a job with an allocation on each,
every allocation there lost and replaced). Its parts, named in
``configs/rackloss-10k.json`` (``fleet`` and ``jobs`` are the defaults,
``gen.fleet`` and ``gen.jobs``: c2m's fleet and mix): ``warm`` (c2m's
pre-fill one job at a time, then the window's own failures until every
shape it reaches is warm), ``driver`` (each arrival takes the next rack of
a fixed stride down through ``NodeHeartbeater.expire``, and brings it back
after its jobs recovered) and ``judge`` (the guarantees of a node's loss,
exactly, and sampled node evals against ``reference/node_loss.py``).
"""
