"""The deployment ``drain-10k``: nodes of c2m-10k's fleet drained one after
another under 50,000 live allocations of services (Nomad's ``node drain``
and the job specification's ``migrate`` stanza: the drainer marks at most
``max_parallel`` allocations of a group a wave, each wave gated on the
clients' health). Its parts, named in ``configs/drain-10k.json`` (``fleet``
is the default, ``gen.fleet``): ``jobs`` (c2m's shapes, services only, each
group with a ``migrate`` block), ``warm`` (c2m's warm-up and pre-fill, every
allocation acknowledged running, then the window's own drains until every
shape it reaches is warm), ``driver`` (each arrival drains the next node of
a fixed stride; the driver also plays the nodes' clients and sets a drained
node eligible again) and ``judge`` (the guarantees of a drain, exactly, and
sampled evals against ``reference/drain.py``).
"""
