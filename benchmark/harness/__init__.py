"""The benchmark's harness: everything a run needs besides the data files.

Nothing here imports the program under test except the child wrapper
(`train_child.py`), which calls its entry point.
"""
