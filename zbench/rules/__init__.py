"""Reference rules for element kinds, one file a kind, found by its name
(``kind: "message_catch"`` -> ``message_catch.py``). A rule module has one
function, ``walk(node_id, node, payload, inputs)``: the element's id and its
node in the ``GRAPH``, the instance's payload when the token arrives, and the
traffic's inputs (``traffic.Inputs`` with ``process`` and ``create`` bound
to this instance). It returns ``(events, jobs, payload, next_node)``: the
workflow-instance events the element adds as (intent, element id, payload),
the jobs it adds as (type, payload at creation, result), the payload after
it, and the node whose outgoing sequence flow the token takes next. A rule
imports nothing of the program."""
