"""The chip benchmark's yardstick: inputs, traffic, reference, work counts,
peaks and the reduction from traces to metrics.

Nothing here imports the program except ``fleet``; the drivers
(``bench/drivers/``) build and drive the system under test.  Everything a
later PR must not be able to change (what is generated, what is compared,
how work and time are counted) lives in this package and in the files it
finds by name (``registry``): the model kinds' reference layers and counts
(``bench/kinds/``) and the graph generators (``bench/generators/``).
"""
