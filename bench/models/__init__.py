"""The system under test, built from a configuration file.

``bench/models/<family>.py`` maps the file's published keys onto the
program's registry entry and makes the weights from the seed; the plain
reference of the same family sits in ``bench/refs/<family>.py``.
"""
