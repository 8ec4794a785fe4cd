"""One runner per traffic kind (``traffic["kind"]``): ``train`` and
``serve``.  Each builds the cell's program object in set-up, drives the
measured window, and reads the numbers the comparison needs."""
