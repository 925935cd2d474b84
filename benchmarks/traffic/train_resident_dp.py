"""Traffic kind ``train_resident_dp``: ``train_resident`` as ``--dp N``
runs it, one program across the ``chips`` of the workload file.

Everything is ``train_resident``'s own: its ``Cell`` already installs
``DataParallel`` over the cell's chips, replicates the resident store
and takes ``minibatch`` as the global one.  The kind has a name of its
own because its load is another one (a global minibatch of ``chips`` x
the one-chip cell's, an all-reduce of every gradient each step), and
``BENCHMARK.json`` takes a pair of configuration and traffic once.
"""

from __future__ import annotations

from . import train_resident


class Cell(train_resident.Cell):
    """One run of a ``train_resident_dp`` cell."""

    def __init__(self, mix, *args, **kw) -> None:
        chips = int(mix.get("chips", 1))
        if chips < 2 or int(mix["minibatch"]) % chips:
            raise ValueError(
                f"train_resident_dp: {mix['name']} asks for {chips} "
                f"chip(s) and a global minibatch of {mix['minibatch']}; "
                f"the kind needs several chips and an equal share each")
        super().__init__(mix, *args, **kw)
