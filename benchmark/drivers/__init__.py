"""One driver per kind of loop; a configuration's ``driver`` key picks."""
