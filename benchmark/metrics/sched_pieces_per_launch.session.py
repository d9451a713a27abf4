ALIAS_OF = "sched_pieces_per_launch"
