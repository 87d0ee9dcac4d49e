"""A deployment that arrives as new files only (the witness of PERF.md
section 3's parts contract).

Five modules, one a part, each wrapping today's module and leaving one mark
on what the contract hands on, so that ``judge`` can hold all five to a
limit of 0 in the stub configuration ``stub_run.py`` lays out:

- ``fleet``: the fleet table gains a column, ``zone``;
- ``jobs``: ``make_job`` sets priority 70;
- ``warm``: the pre-fill returns its own ``steady_jobs``;
- ``driver``: counts completions, and stamps each with the count and with
  the ``steady_jobs`` it was handed;
- ``judge``: reads the four marks back from the fleet table, the store and
  the requests, and adds a number of its own.

No existing file names any of these modules.
"""
