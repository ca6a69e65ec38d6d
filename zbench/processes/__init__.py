"""One process per file, found by name (``order-process`` ->
``order_process.py``). ``GRAPH`` is plain data the reference walks;
``build()`` makes the program's model of the same graph."""
