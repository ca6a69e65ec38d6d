"""An intermediate message catch event. The node carries ``message`` (the
message's name) and ``correlation_var``: the payload variable whose value,
as a string, is the correlation key. The token waits at the element until
the message is correlated, then leaves by its one outgoing flow.

The events follow the BPMN lifecycle in the protocol's intents
(``WorkflowInstanceIntent``): the element is READY, then ACTIVATED (its
subscription opens); the correlation completes it, COMPLETING with the
message's payload alone, as a service task's COMPLETING carries the job's
result, then COMPLETED with that payload merged into the instance's
(no output mapping: the merge is the default behaviour)."""

ELEMENT_READY, ELEMENT_ACTIVATED, ELEMENT_COMPLETING, ELEMENT_COMPLETED = 6, 7, 8, 9


def walk(node_id: str, node: dict, payload: dict, inputs) -> tuple:
    key = str(payload[node["correlation_var"]])
    if key != str(inputs.create[node["correlation_var"]]):
        raise ValueError(
            f"{node_id}: correlation key {key!r} is not the one the traffic publishes for"
        )
    message = inputs.message_for(inputs.process, inputs.create)
    after = {**payload, **message}
    events = [
        (ELEMENT_READY, node_id, dict(payload)),
        (ELEMENT_ACTIVATED, node_id, dict(payload)),
        (ELEMENT_COMPLETING, node_id, dict(message)),
        (ELEMENT_COMPLETED, node_id, dict(after)),
    ]
    return events, [], after, node_id
