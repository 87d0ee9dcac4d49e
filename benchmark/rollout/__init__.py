"""The deployment ``rollout-10k``: new versions of live jobs rolled out
over c2m-10k's fleet (Nomad's ``update`` stanza: a service replaces
``max_parallel`` allocations at a time on its clients' health verdicts, a
batch job is replaced all at once). Its parts, named in
``configs/rollout-10k.json`` (``fleet`` is the default, ``gen.fleet``):
``jobs`` (c2m's shapes with an ``update`` block and a version in one task
``env`` value), ``warm`` (c2m's warm-up and pre-fill, then the window's own
arrivals until every shape it reaches is warm), ``driver`` (each arrival
registers the next version of the job updated longest ago; the driver also
plays the nodes' clients) and ``judge`` (the guarantees of a rollout,
exactly, and sampled evals against ``reference/rollout.py``).
"""
