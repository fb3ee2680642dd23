"""`bert-base`: what decides `correct`, and where its limits come from.

The comparison itself is lib/train_check.py (the timed step followed
through three steps by reference.py) and traffic/kinds/train_stream.py
(counts). The limits are in config.json under "check", each with the
readings it was set from; evidence/ holds the output of
`check_tolerances.py` that produced them. Nothing here compares a loss to
more digits than the band measured over seeds, and no token is compared."""
