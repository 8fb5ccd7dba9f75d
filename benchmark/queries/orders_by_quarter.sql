select o_orderpriority, count(*)
from orders
where o_orderdate >= date '{d0}' and o_orderdate < date '{d1}'
group by o_orderpriority order by o_orderpriority
