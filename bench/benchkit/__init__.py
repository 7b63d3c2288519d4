"""The benchmark's own library: cells, drivers, references, trace reduction.

Nothing here is imported by the program under test; ``bench/run.py`` puts
this directory and the checkout's ``src`` on ``sys.path``.
"""
