"""The benchmark's harness: everything a run needs besides the data files.

Nothing here imports the program under test except the two child wrappers
(`train_child.py`), which call its entry point.
"""
