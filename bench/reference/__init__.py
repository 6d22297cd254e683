"""The plain reference the benchmark compares the program with.

``cluster.py`` runs one DRS cluster under CloudPowerCap tick by tick, in
scalar per-host code written from the paper's algorithms; ``scenario.py``
builds each cluster itself from the fields the benchmark's grid generator
gives.  It imports nothing of the program and takes nothing the program
made.  ``bench/tests/test_reference.py`` checks it, as a second witness,
against the program's object ``Simulator`` on the CPU.
"""
