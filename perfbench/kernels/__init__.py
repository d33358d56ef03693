"""One module per kernel, named by a metric file's ``kernel``:
``least_seconds(spec, facts, count, peak)``, the least seconds a chip of
the published peaks ``peak`` could take for the ``count`` matched events."""
