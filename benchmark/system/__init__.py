"""The deployment ``system-10k``: a new version of a node agent (Nomad's
``system`` scheduler) rolled onto every node of c2m-10k's fleet under live
services. Its parts, named in ``configs/system-10k.json`` (``fleet`` is the
default, ``gen.fleet``): ``jobs`` (the node agents, one system job each,
and c2m's services), ``warm`` (the agents registered, one update of each
agent shape, c2m's services one job at a time), ``driver`` (each arrival
registers the next version of the agent updated longest ago; done when its
eval is complete and every node holds the new version) and ``judge`` (the
guarantees of an update, exactly, and every replacement's score against
``reference/system.py``). ``control.py`` puts the reference in the
program's place.
"""
